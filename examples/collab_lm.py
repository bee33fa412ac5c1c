"""CoRS across *heterogeneous LM architectures* — the paper's
model-heterogeneity selling point at LM scale: a (reduced) llama-family
client and a (reduced) xLSTM client collaborate purely through per-class
(= per-next-token) feature representations. No weights cross the boundary,
so the architectures never need to match.

The fleet need not be synchronous either: `--clock-model` commits each
client's prototype stats late through the bounded-delay pending buffer
(launch.train.make_async_round_sync — the LM-scale counterpart of the
engines' event-ordered relay), and `--download-clock` serves each client
the global prototypes from a past round via the relay history ring
(src/repro/relay/history.py). `--telemetry-out` streams per-round records
(CE, late/stale counters, prototype drift/mass/coverage from
launch.train.proto_round_telemetry) to a JSONL the run-report CLI renders.

  PYTHONPATH=src python examples/collab_lm.py [--rounds R]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs, sim
from repro.configs import get_arch
from repro.core import losses, prototypes
from repro.data import synthetic
from repro.launch import train as launch_train
from repro.models import lm
from repro.optim import adam_init, adam_update
from repro.relay import history as relay_history
from repro.types import CollabConfig

VOCAB = 256
SEQ = 64
BATCH = 8
STEPS_PER_ROUND = 8


def make_client(arch: str, key):
    cfg = get_arch(arch).reduced(vocab_size=VOCAB)
    params = lm.init_lm(key, cfg)
    return {"cfg": cfg, "params": params, "opt": adam_init(params)}


def local_round(client, batches, proto_means, lam_kd, lam_disc, key):
    cfg = client["cfg"]

    def loss_fn(params, batch, k):
        out = lm.forward(params, cfg, {"tokens": batch["tokens"]})
        feats, logits = out["features"], out["logits"]
        labels = batch["labels"]
        l_ce = losses.ce_loss(logits, labels)
        l_kd = losses.kd_loss(feats, proto_means, labels)
        f = feats.reshape(-1, feats.shape[-1])[:64]
        y = labels.reshape(-1)[:64]
        l_disc = losses.disc_loss_sampled(
            k, f, proto_means, y, params["lm_head"], num_negatives=32,
            student_logits=logits.reshape(-1, VOCAB)[:64])
        return l_ce + lam_kd * l_kd + lam_disc * l_disc, (l_ce, feats, labels)

    @jax.jit
    def step(params, opt, batch, k):
        (_, (ce, feats, labels)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, k)
        params, opt = adam_update(params, g, opt)
        return params, opt, ce, feats, labels

    stats = prototypes.init_state(VOCAB, cfg.d_model)
    ce = 0.0
    for i, batch in enumerate(batches):
        key, k = jax.random.split(key)
        client["params"], client["opt"], ce, feats, labels = step(
            client["params"], client["opt"], batch, k)
        stats = prototypes.accumulate(stats, feats.reshape(-1, cfg.d_model),
                                      labels.reshape(-1))
    return float(ce), stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clock-model", default="none",
                    help="virtual-time upload clock (repro.sim): none | "
                         "homogeneous[:delay] | lognormal[:dmax[,sigma]] | "
                         "periodic[:dmax[,period]] — a client's round-r "
                         "prototype stats join the shared state in round "
                         "r+delay via the bounded-delay pending buffer")
    ap.add_argument("--download-clock", default="none",
                    help="download-lag clock (same spec zoo, independent "
                         "randomness): clients read the global prototypes "
                         "of round t-d from the relay history ring instead "
                         "of this round's fresh merge")
    ap.add_argument("--telemetry-out", default=None, metavar="RUN.jsonl",
                    help="stream per-round records (CE, late/stale "
                         "counters, prototype drift) to this JSONL file "
                         "(render with `python -m repro.obs.report "
                         "RUN.jsonl`)")
    args = ap.parse_args()

    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    clients = [make_client("tinyllama-1.1b", keys[0]),
               make_client("xlstm-125m", keys[1])]
    # NOTE: d_model of both reduced archs must match for shared prototypes;
    # reduced() gives 256-dim features for both families here.
    assert clients[0]["cfg"].d_model == clients[1]["cfg"].d_model
    d = clients[0]["cfg"].d_model
    n = len(clients)

    stream = synthetic.token_stream(100_000, vocab=VOCAB, seed=0)
    splits = [stream[:50_000], stream[50_000:]]      # private corpora

    # fleet clocking: the upload clock feeds the bounded-delay pending
    # buffer (late stats commit in their due round, order-free because the
    # prototype merge is a sum); the download clock indexes the history
    # ring of post-merge snapshots. Both degenerate exactly to the
    # synchronous loop at d_max = 0.
    clock = sim.get_clock(args.clock_model, seed=7)
    dl_clock = sim.get_download_clock(args.download_clock, seed=7)
    d_max = clock.d_max if clock is not None else 0
    h_max = (dl_clock.d_max + 1) if dl_clock is not None else 1
    ccfg = CollabConfig(mode="cors", num_classes=VOCAB, d_feature=d)
    init_pending, round_sync = launch_train.make_async_round_sync(ccfg, d_max)
    pending = init_pending(VOCAB, d)
    hist = relay_history.init(prototypes.init_state(VOCAB, d), h_max)

    writer = (obs.JsonlWriter(args.telemetry_out)
              if args.telemetry_out else None)
    global_state = prototypes.init_state(VOCAB, d)
    late_total = stale_total = 0
    key = jax.random.PRNGKey(42)
    print(f"clients: tinyllama-reduced + xlstm-reduced, vocab={VOCAB}, "
          f"clock={args.clock_model}, download={args.download_clock}")
    print("round  ce[llama]  ce[xlstm]  comm_MB/round")
    for r in range(args.rounds):
        dl = (dl_clock.delays(r, n) if dl_clock is not None
              else np.zeros((n,), np.int64))
        round_stats = []
        ces = []
        for i, (c, corp) in enumerate(zip(clients, splits)):
            # each client trains against the snapshot its download clock
            # last synced — round r - dl[i]'s post-merge prototypes
            proto_means = prototypes.means(
                relay_history.read_at(hist, int(dl[i])))
            key, k1, k2 = jax.random.split(key, 3)
            batches = list(synthetic.lm_batches(
                corp, BATCH, SEQ, STEPS_PER_ROUND,
                seed=int(jax.random.randint(k1, (), 0, 1 << 30))))
            batches = [{k: jnp.asarray(v) for k, v in b.items()}
                       for b in batches]
            ce, stats = local_round(c, batches, proto_means, 1.0, 0.1, k2)
            ces.append(ce)
            round_stats.append(stats)
        # the only exchange: this round's due stats (fresh delay-0 ones
        # plus pending arrivals) merge into a fresh global state, exactly
        # `prototypes.merge(*round_stats)` when the fleet is synchronous
        delays = (clock.delays(r, n) if clock is not None
                  else np.zeros((n,), np.int64))
        stacked = prototypes.ProtoState(
            jnp.stack([s.sum for s in round_stats]),
            jnp.stack([s.count for s in round_stats]))
        state = launch_train.TrainState(
            None, None, prototypes.init_state(VOCAB, d),
            jnp.zeros((), jnp.int32))
        state, pending = round_sync(state, pending,
                                    jnp.asarray(delays, jnp.int32), stacked)
        prev_state, global_state = global_state, state.proto
        hist = relay_history.push(hist, global_state)
        late = int(np.sum(delays > 0))
        stale = int(np.sum(dl > 0))
        late_total += late
        stale_total += stale
        comm_floats = 2 * n * VOCAB * (d + 1)            # up+down, all clients
        comm_mb = comm_floats * 4 / 1e6
        print(f"{r + 1:4d}   {ces[0]:.4f}    {ces[1]:.4f}    {comm_mb:.3f}")
        if writer:
            writer.write({
                "round": r,
                "participants": list(range(n)),
                "ce": {c["cfg"].name: ce for c, ce in zip(clients, ces)},
                "late_commits": late, "stale_reads": stale,
                "comm_up": comm_floats / 2, "comm_down": comm_floats / 2,
                "proto_telemetry": launch_train.proto_round_telemetry(
                    prev_state, global_state),
            })
    if writer:
        writer.close()

    # fleet health — the same counters the collaborative engines surface
    # through repro.obs telemetry, reduced from this loop's own clocks
    if late_total:
        print(f"async prototype relay: {late_total} client-round stat "
              f"uploads committed late (bounded-delay pending, see "
              f"src/repro/launch/train.py)")
    if stale_total:
        print(f"download lag: {stale_total} client-rounds trained against "
              f"a stale prototype snapshot (history ring, see "
              f"src/repro/relay/history.py)")
    if args.telemetry_out:
        print(f"telemetry: {args.telemetry_out} (render with "
              f"`python -m repro.obs.report {args.telemetry_out}`)")
    print("\nheterogeneous-arch collaboration ran end-to-end; the exchanged "
          "state is (V, d'+1) floats per client per round, independent of "
          "either model's size.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
