"""End-to-end driver (paper's own scenario, Table 1 regime): N=5 clients,
sparse local data, CoRS vs IL vs FedAvg over many rounds with eval + exact
communication accounting + checkpointing of every client model.

  PYTHONPATH=src python examples/collab_image_classification.py [--rounds R]
"""
import argparse
import os

import jax

from repro import compile_cache, obs
from repro.checkpoint import save_pytree
from repro.core import client as client_lib, collab, vec_collab
from repro.data import partition, synthetic
from repro.models import cnn, mlp
from repro.types import CollabConfig, FleetConfig, TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--mode", default="cors",
                    choices=["cors", "il", "fd", "fedavg"])
    ap.add_argument("--lambda-kd", type=float, default=2.0)
    ap.add_argument("--lambda-disc", type=float, default=1.0)
    ap.add_argument("--engine", default="vec", choices=["vec", "seq"],
                    help="vec = one vmapped round step per client bucket "
                         "(default); seq = per-client Python-loop oracle")
    ap.add_argument("--hetero", action="store_true",
                    help="mixed fleet (a CoRS selling point): odd client "
                         "ids run an MLP instead of the LeNet; the vec "
                         "engine buckets them (2 vmapped steps sharing one "
                         "relay), no weights ever cross architectures")
    ap.add_argument("--relay-policy", default="flat",
                    help="server-side relay policy: flat | per_class | "
                         "staleness[:lam] (see src/repro/relay/README.md)")
    ap.add_argument("--participation", default="full",
                    help="per-round client participation schedule: full | "
                         "uniform_k:K | cyclic:K | bernoulli:P | "
                         "adaptive:P[,BOOST] (adaptive boosts observed "
                         "stragglers; e.g. uniform_k:2 = 2 random clients "
                         "per round)")
    ap.add_argument("--clock-model", default="none",
                    help="virtual-time client clock driving the async "
                         "event-ordered relay (repro.sim): none | "
                         "homogeneous[:delay] | lognormal[:dmax[,sigma]] | "
                         "periodic[:dmax[,period]] — e.g. lognormal:4 is a "
                         "straggler fleet whose uploads commit up to 4 "
                         "rounds late, in event order")
    ap.add_argument("--download-clock", default="none",
                    help="download-lag clock (same spec zoo as "
                         "--clock-model, independent randomness): clients "
                         "read teachers and global prototypes from the "
                         "relay snapshot of round t-d via the bounded "
                         "history ring (src/repro/relay/history.py) — "
                         "e.g. periodic:3,4 is a duty-cycled fleet "
                         "training against up-to-3-round-stale syncs")
    ap.add_argument("--telemetry-out", default=None, metavar="RUN.jsonl",
                    help="stream per-round telemetry records to this JSONL "
                         "file (render with `python -m repro.obs.report "
                         "RUN.jsonl`); telemetry metrics are on either "
                         "way — this adds the durable sink")
    ap.add_argument("--out", default="artifacts/collab_ckpt")
    args = ap.parse_args()

    x, y = synthetic.class_images(1200, seed=0, noise=0.8)
    tx, ty = synthetic.class_images(2000, seed=99, noise=0.8)
    parts = partition.uniform_split(x, y, args.clients, seed=1)
    print(f"{args.clients} clients × {len(parts[0][0])} samples each, "
          f"mode={args.mode}, relay={args.relay_policy}, "
          f"participation={args.participation}, clock={args.clock_model}, "
          f"download={args.download_clock}"
          + (", hetero cnn/mlp fleet" if args.hetero else ""))

    cnn_spec = client_lib.ClientSpec(
        apply=lambda p, xx: cnn.apply(p, xx),
        head=lambda p: (p["head_w"], p["head_b"]))
    mlp_spec = client_lib.ClientSpec(
        apply=lambda p, xx: mlp.apply(p, xx),
        head=lambda p: (p["head_w"], p["head_b"]))
    keys = jax.random.split(jax.random.PRNGKey(0), args.clients)
    if args.hetero:
        specs = [cnn_spec if i % 2 == 0 else mlp_spec
                 for i in range(args.clients)]
        params = [cnn.init_cnn(k) if i % 2 == 0 else mlp.init_mlp(k)
                  for i, k in enumerate(keys)]
    else:
        specs = [cnn_spec] * args.clients
        params = [cnn.init_cnn(k) for k in keys]
    ccfg = CollabConfig(mode=args.mode, num_classes=10, d_feature=84,
                        lambda_kd=args.lambda_kd,
                        lambda_disc=args.lambda_disc)
    cls = (vec_collab.VectorizedCollabTrainer if args.engine == "vec"
           else collab.CollabTrainer)
    trainer = cls(specs, params, parts,
                  (tx, ty), ccfg, TrainConfig(batch_size=32), seed=0,
                  telemetry=obs.TelemetryConfig(jsonl=args.telemetry_out),
                  fleet=FleetConfig(policy=args.relay_policy,
                                    participation=args.participation,
                                    clock=args.clock_model,
                                    download_clock=args.download_clock))
    trainer.run(args.rounds, log_every=max(1, args.rounds // 15))
    # fleet health from the engine's own telemetry (repro.obs) — the same
    # counters both engines oracle-check, not recomputed driver-side
    telem = [h["telemetry"] for h in trainer.history]
    late = sum(sum(t["commit_hist"][1:]) for t in telem)
    if late:
        print(f"async relay: {late} uploads committed late "
              f"(event-ordered, see src/repro/relay/events.py)")
    stale = sum(t["stale_reads"] for t in telem)
    if stale:
        print(f"download lag: {stale} client-rounds trained against a "
              f"stale relay snapshot (history ring, see "
              f"src/repro/relay/history.py)")
    if args.telemetry_out:
        print(f"telemetry: {args.telemetry_out} (render with "
              f"`python -m repro.obs.report {args.telemetry_out}`)")

    os.makedirs(args.out, exist_ok=True)
    for i in range(args.clients):
        p = (trainer.client_params(i) if args.engine == "vec"
             else trainer.clients[i].params)
        save_pytree(os.path.join(args.out, f"client{i}.npz"), p,
                    step=args.rounds)
    best = max(h["acc_mean"] for h in trainer.history)
    print(f"\nbest mean accuracy: {best:.4f}; "
          f"total comm {trainer.ledger.total_bytes/1e6:.2f} MB; "
          f"checkpoints in {args.out}/")


if __name__ == "__main__":
    compile_cache.enable()
    main()
