"""Smoke run of the program's main paths on the chip, through the entry
points a user calls.

  python chip_smoke.py               # one chip: fleet phase + LM phase
  python chip_smoke.py --four-chips  # four chips: meshed fleet vs unmeshed

Default phases (one chip):

- fleet: the paper's scenario. N=32 LeNet5 clients at the published widths
  (28x28x1 input, conv 6/16, fc 120, d'=84, C=10) train through a flat relay
  in the vectorized fleet engine (`VectorizedCollabTrainer`, mode "cors"),
  built as `benchmarks.common.make_trainer` builds every benchmark fleet.
  Three rounds with eval are timed. A second vectorized fleet and the
  sequential oracle (`CollabTrainer`) then run two rounds from the same
  seeds, and must agree: ring bookkeeping exactly, floats within the
  tolerances stated below.
- lm: `launch.train.make_train_step` for the whole xlstm-125m (12 layers,
  d_model 768, vocab 50304) in CoRS mode with one client, three steps on
  one seeded 8x1024 token batch. Every metric must be finite and the CE on
  that fixed batch must not rise.

`--four-chips` runs only the N=32 fleet on `sharding.client_mesh(4)`
against the same fleet with no mesh, in this one process: equal relay
state, close params, one compilation of the meshed round step, and client
stacks spread over the four devices.

Each phase prints one JSON line (name, compile and steady seconds, the
comparison, the device kind). The last line is the result,
`{"ok": true, "device": {...}}`, and only appears when every phase passed
on a TPU. On any other platform the phases run as a rehearsal, with the LM
at a reduced size so that it fits a CPU, and the script exits 1 without a
result. A failed check raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from oracles import assert_ledgers_equal, assert_states_match, run_matched  # noqa: E402
from repro import compile_cache, sharding  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.types import CollabConfig, FleetConfig  # noqa: E402

N_CLIENTS = 32
SAMPLES_PER_CLIENT = 96          # three local batches of 32 per round
N_TEST = 2000
LM_ARCH = "xlstm-125m"
LM_BATCH, LM_SEQ = 8, 1024
LM_STEPS = 3
T_START = time.perf_counter()

# Tolerances of the float comparisons. Both sides run with
# jax.default_matmul_precision("highest"): a float32 matmul or conv on a TPU
# otherwise runs in bf16 passes, and the two sides would differ by bf16
# rounding (~4e-3 relative) rather than by summation order.
#
# Client params: the vmapped fleet and the per-client loop sum the same
# float32 terms in different orders (batched convs, reductions over the
# client axis), so gradients differ in the last bits. Adam divides by
# sqrt(v), which turns a last-bit difference of a near-zero gradient into
# an update difference of up to lr = 1e-3 per step; few weights sit there,
# and 1e-3 bounds one such step while catching a wrong client, key or
# relay read, which moves whole layers by far more.
PARAM_ATOL = 1e-3
# Observations and global prototypes: tests/oracles.py's 5e-3 (features of
# O(1) magnitude after the same reordering), and its 2e-2 on accuracies
# (one test sample in 2000 is 5e-4; 2e-2 allows a few flipped argmaxes).


def log(msg: str) -> None:
    """Progress on stderr, so that a phase that fails shows how far it got."""
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def fleet(engine: str, mesh=None):
    """The smoke's fleet, built the way every benchmark fleet is."""
    x, y = synthetic.class_images(N_CLIENTS * SAMPLES_PER_CLIENT, seed=0,
                                  noise=0.8)
    test = synthetic.class_images(N_TEST, seed=99, noise=0.8)
    return common.make_trainer(
        "cors", N_CLIENTS, engine=engine, batch_size=32,
        train_data=(x, y), test_data=test,
        fleet=FleetConfig(policy="flat", mesh=mesh))


def timed_rounds(trainer, rounds: int):
    """Run `rounds` rounds; -> seconds of each, host clock around work that
    ends on the host (run_round pulls metrics and accuracies back)."""
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        rec = trainer.run_round()
        jax.block_until_ready(trainer.params)
        out.append(time.perf_counter() - t0)
        if not np.all(np.isfinite(rec["accs"])):
            raise FloatingPointError(f"non-finite accuracy: {rec['accs']}")
    return out


def max_param_diff(a, b) -> float:
    d = jax.tree.map(lambda u, v: float(np.max(np.abs(
        np.asarray(u, np.float64) - np.asarray(v, np.float64)))), a, b)
    return max(jax.tree.leaves(d))


def check_params_close(trainer, ref_params) -> float:
    worst = 0.0
    for i in range(N_CLIENTS):
        worst = max(worst, max_param_diff(trainer.client_params(i),
                                          ref_params(i)))
    if not worst <= PARAM_ATOL:
        raise AssertionError(f"client params differ by {worst} > {PARAM_ATOL}")
    return worst


def phase_fleet(kind: str) -> dict:
    vec = fleet("vec")
    secs = timed_rounds(vec, 3)
    log(f"fleet: 3 timed rounds {secs}")
    with jax.default_matmul_precision("highest"):
        seq, vec2 = fleet("seq"), fleet("vec")
        run_matched(seq, vec2, rounds=2)
        worst = check_params_close(vec2, lambda i: seq.clients[i].params)
    log("fleet: vec == seq oracle")
    return {"phase": "fleet", "clients": N_CLIENTS,
            "first_round_with_compile_s": secs[0], "steady_round_s": secs[1:],
            "acc_mean": vec.history[-1]["acc_mean"],
            "check": "vec == seq oracle over 2 rounds: ring bookkeeping "
                     "exact, ledgers exact, obs/protos within 5e-3, params "
                     f"max |d| = {worst:.3g} <= {PARAM_ATOL}",
            "device_kind": kind}


def phase_lm(kind: str, full_size: bool) -> dict:
    cfg = get_arch(LM_ARCH)
    batch_size, seq_len = LM_BATCH, LM_SEQ
    if not full_size:
        # reduced widths, but two of xlstm's 256-token chunks per sequence
        cfg, batch_size, seq_len = cfg.reduced(), 2, 512
    ccfg = CollabConfig(mode="cors", num_classes=cfg.vocab_size,
                        d_feature=cfg.d_feature, num_negatives=1023)
    step = train_lib.make_train_step(cfg, ccfg, n_clients=1)
    state = train_lib.init_state(cfg, jax.random.PRNGKey(0), n_clients=1)
    toks = synthetic.token_stream(batch_size * seq_len + seq_len + 1,
                                  vocab=cfg.vocab_size, seed=0)
    b = next(synthetic.lm_batches(toks, batch_size, seq_len, 1, seed=0))
    batch = {k: jnp.asarray(v)[None] for k, v in b.items()}
    key = jax.random.PRNGKey(1)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=0).lower(state, batch,
                                                     key).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    log(f"lm: compiled in {compile_s:.1f}s; {mem}")
    secs, ce, total = [], [], []
    for i in range(LM_STEPS):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch, jax.random.fold_in(key, i))
        jax.block_until_ready((state, metrics))
        secs.append(time.perf_counter() - t0)
        m = {k: float(v) for k, v in metrics.items()}
        log(f"lm: step {i} {secs[-1]:.3f}s {m}")
        if not all(np.isfinite(v) for v in m.values()):
            raise FloatingPointError(f"step {i}: non-finite metrics {m}")
        ce.append(m["ce"])
        total.append(m["total"])
    # The CE on a fixed batch is the objective that must not rise. The
    # total also holds the KD and disc terms against prototypes that each
    # step refreshes from its own features, so it is reported, not checked.
    if any(b > a for a, b in zip(ce, ce[1:])):
        raise AssertionError(f"CE rose over {LM_STEPS} steps: {ce}")
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    return {"phase": "lm", "arch": cfg.name, "params": int(n_params),
            "batch": [batch_size, seq_len], "compile_s": compile_s,
            "step_s": secs, "ce": ce, "total_loss": total,
            "bytes": {k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")},
            "check": f"{LM_STEPS} finite steps, CE non-increasing",
            "device_kind": kind}


def phase_four_chips(kind: str) -> list:
    n_dev = len(jax.devices())
    if n_dev != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {n_dev}")
    mesh = sharding.client_mesh(4)
    with jax.default_matmul_precision("highest"):
        plain, meshed = fleet("vec"), fleet("vec", mesh=mesh)
        s_plain, s_mesh = [], []
        for _ in range(3):
            s_plain += timed_rounds(plain, 1)
            s_mesh += timed_rounds(meshed, 1)
            rp, rm = plain.history[-1], meshed.history[-1]
            if rp["participants"] != rm["participants"] \
                    or rp["commits"] != rm["commits"]:
                raise AssertionError("participants or commits differ")
            np.testing.assert_allclose(rp["accs"], rm["accs"], atol=2e-2)
        assert_ledgers_equal(plain.ledger, meshed.ledger)
        assert_states_match(plain.relay_state, meshed.relay_state)
        worst = check_params_close(meshed, plain.client_params)
    n_compiles = meshed._round_step._cache_size()
    if n_compiles != 1:
        raise AssertionError(f"mesh round step compiled {n_compiles} times")
    per_leaf = [sorted((s.device.id, s.data.shape[0])
                       for s in leaf.addressable_shards)
                for leaf in jax.tree.leaves(meshed.params)]
    want = sorted((d.id, N_CLIENTS // 4) for d in mesh.devices.flat)
    if any(p != want for p in per_leaf):
        raise AssertionError(f"client stacks not spread over 4 devices: "
                             f"{per_leaf[0]}")
    return [{"phase": "fleet_no_mesh", "clients": N_CLIENTS,
             "first_round_with_compile_s": s_plain[0], "steady_round_s": s_plain[1:],
             "device_kind": kind},
            {"phase": "fleet_mesh4", "clients": N_CLIENTS,
             "first_round_with_compile_s": s_mesh[0], "steady_round_s": s_mesh[1:],
             "round_step_compilations": n_compiles,
             "client_shards": [f"device {d}: {n} clients" for d, n in want],
             "check": "mesh == no mesh over 3 rounds: ring bookkeeping "
                      "exact, ledgers exact, obs/protos within 5e-3, params "
                      f"max |d| = {worst:.3g} <= {PARAM_ATOL}",
             "device_kind": kind}]


def emit(*lines: dict) -> None:
    for line in lines:
        print(json.dumps(line), flush=True)


def main() -> int:
    log(f"compile cache: {compile_cache.enable()}")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device mesh fleet and the "
                         "unmeshed fleet it is compared with")
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if args.four_chips:
        emit(*phase_four_chips(dev.device_kind))
    else:
        emit(phase_fleet(dev.device_kind))
        emit(phase_lm(dev.device_kind, full_size=on_tpu))
    if not on_tpu:
        print(f"rehearsal on platform {dev.platform!r}: phases ran, but "
              "only a TPU run gives a result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
