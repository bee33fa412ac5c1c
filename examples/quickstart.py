"""Quickstart: 60 seconds with the CoRS framework on CPU.

Trains two collaborating clients (different random inits, private data
shards) with the paper's objective L_CE + λ_KD·L_KD + λ_disc·L_disc, and
prints per-round accuracy plus the exact communication ledger.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro import compile_cache
from repro.core import client as client_lib, collab
from repro.data import partition, synthetic
from repro.models import cnn
from repro.types import CollabConfig, TrainConfig


def main():
    x, y = synthetic.class_images(600, seed=0, noise=0.6)
    tx, ty = synthetic.class_images(800, seed=9, noise=0.6)
    parts = partition.uniform_split(x, y, 2, seed=1)

    spec = client_lib.ClientSpec(
        apply=lambda p, xx: cnn.apply(p, xx),
        head=lambda p: (p["head_w"], p["head_b"]))
    params = [cnn.init_cnn(k)
              for k in jax.random.split(jax.random.PRNGKey(0), 2)]

    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84,
                        lambda_kd=2.0, lambda_disc=1.0)
    trainer = collab.CollabTrainer([spec] * 2, params, parts, (tx, ty),
                                   ccfg, TrainConfig(batch_size=32), seed=0)
    print("round  acc_mean  acc_std   L_CE    L_KD    L_disc   MI-bound")
    for _ in range(8):
        rec = trainer.run_round()
        m = rec["metrics"][0]
        print(f"{rec['round']:4d}   {rec['acc_mean']:.4f}   "
              f"{rec['acc_std']:.4f}  {m['ce']:.3f}  {m.get('kd', 0):.4f}  "
              f"{m.get('disc', 0):.3f}  {m.get('mi_bound', 0):+.3f} nats")
    mb = trainer.ledger.total_bytes / 1e6
    print(f"\ntotal communication: {mb:.2f} MB "
          f"(FedAvg would have used "
          f"{cnn.num_params(params[0]) * 2 * 8 * 4 / 1e6:.1f} MB)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
