"""Shared benchmark harness utilities."""
from __future__ import annotations

import os
import time
import jax

from repro.core import client as client_lib, collab, vec_collab
from repro.data import partition, synthetic
from repro.models import cnn, mlp
from repro.types import CollabConfig, FleetConfig, TrainConfig

ROUNDS = int(os.environ.get("REPRO_BENCH_ROUNDS", "12"))
N_TRAIN = int(os.environ.get("REPRO_BENCH_TRAIN", "1200"))
N_TEST = int(os.environ.get("REPRO_BENCH_TEST", "2000"))
NOISE = float(os.environ.get("REPRO_BENCH_NOISE", "0.8"))

SPEC = client_lib.ClientSpec(
    apply=lambda p, x: cnn.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))

MLP_SPEC = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))


def data(seed=0):
    x, y = synthetic.class_images(N_TRAIN, seed=seed, noise=NOISE)
    tx, ty = synthetic.class_images(N_TEST, seed=seed + 99, noise=NOISE)
    return (x, y), (tx, ty)


def hetero_fleet(mix: str, n_clients: int, seed: int = 0):
    """Build a mixed-architecture fleet from a mix spec like
    "mlp:64,mlp:128" or "mlp:64,cnn:1" — entries are model:size
    (mlp hidden width / cnn width multiplier), assigned round-robin so
    buckets interleave across client ids (the hard case for the bucketed
    engine's ordering). ONE ClientSpec object per entry, shared by all of
    that entry's clients, so `client_lib.bucketize` stacks them."""
    entries = []
    for item in mix.split(","):
        model, _, size = item.strip().partition(":")
        size = int(size) if size else (64 if model == "mlp" else 1)
        if model == "mlp":
            spec = client_lib.ClientSpec(
                apply=lambda p, x: mlp.apply(p, x),
                head=lambda p: (p["head_w"], p["head_b"]))
            init = lambda k, h=size: mlp.init_mlp(k, hidden=h)
        elif model == "cnn":
            spec = client_lib.ClientSpec(
                apply=lambda p, x: cnn.apply(p, x),
                head=lambda p: (p["head_w"], p["head_b"]))
            init = lambda k, w=size: cnn.init_cnn(k, width=w)
        else:
            raise ValueError(f"unknown hetero mix entry: {item!r}")
        entries.append((spec, init))
    keys = jax.random.split(jax.random.PRNGKey(seed), n_clients)
    specs = [entries[i % len(entries)][0] for i in range(n_clients)]
    params = [entries[i % len(entries)][1](k) for i, k in enumerate(keys)]
    return specs, params


def make_trainer(mode: str, n_clients: int, *, lambda_kd: float = 10.0,
                 lambda_disc: float = 1.0, seed: int = 0, width: int = 1,
                 engine: str = "vec", batch_size: int = 32,
                 train_data=None, test_data=None, model: str = "cnn",
                 policy=None, participation=None, hetero: str = None,
                 clock=None, download_clock=None, mesh=None, arrivals=None,
                 fleet=None, telemetry=None):
    """Build a trainer without running it. engine: "vec" (default — ALL
    benchmark fleets go through the vectorized engine, whose round is one
    jitted step over the fleet's client buckets; there is no seq
    special-case for heterogeneous specs) or "seq" (the per-client
    Python-loop oracle, any mix). model: "cnn" (paper's LeNet) or "mlp"
    (cheap-compute client, see models/mlp.py). hetero: a `hetero_fleet`
    mix spec (e.g. "mlp:64,mlp:128") that overrides `model`/`width` with a
    mixed-architecture fleet. policy / participation: relay-policy and
    participation-schedule specs forwarded to the trainer (see
    repro.relay.get_policy / get_schedule), e.g. policy="per_class",
    participation="uniform_k:8". clock: a repro.sim ClockModel spec (e.g.
    "lognormal:4") driving the asynchronous event-ordered relay.
    download_clock: a repro.sim download-lag spec (e.g. "lognormal:4") —
    clients read stale relay snapshots from the bounded history ring
    (repro.relay.history). mesh: a jax Mesh with a "clients" axis — the
    placement-aware device path (repro.relay.placement). arrivals: a
    streaming-population spec (repro.sim.get_arrivals, e.g.
    "stream:3,2.0,0.2,100000,0") — clients join/leave an unbounded id
    space over `n_clients` SEATS, and participation is owned by the
    cohort table. The loose kwargs are this helper's own shorthand: they
    are folded into the `repro.types.FleetConfig` the trainers take. fleet:
    pass a ready-made FleetConfig instead (mixing it with the loose
    policy/participation/clock/download_clock/mesh/arrivals kwargs is an
    error). telemetry: forwarded to the
    trainer (True or a repro.obs.TelemetryConfig; None = off — the
    benchmark default, so timings measure the telemetry-free program; the
    `telemetry` CI gate measures the on/off delta explicitly)."""
    if train_data is None or test_data is None:
        (x, y), test = data(seed)
    else:
        (x, y), test = train_data, test_data
    if mode == "cl":
        parts = [(x, y)]
        n_clients = 1
        mode_eff = "il"
    else:
        parts = partition.uniform_split(x, y, n_clients, seed=seed + 1)
        mode_eff = mode
    ccfg = CollabConfig(mode=mode_eff, num_classes=10, d_feature=84,
                        lambda_kd=lambda_kd if mode_eff in ("cors", "fd")
                        else 0.0,
                        lambda_disc=lambda_disc if mode_eff == "cors" else 0.0)
    tcfg = TrainConfig(batch_size=batch_size)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_clients)
    if hetero is not None:
        specs, params = hetero_fleet(hetero, n_clients, seed=seed)
    elif model == "mlp":
        specs = [MLP_SPEC] * n_clients
        params = [mlp.init_mlp(k, hidden=64 * width) for k in keys]
    else:
        specs = [SPEC] * n_clients
        params = [cnn.init_cnn(k, width=width) for k in keys]
    cls = (vec_collab.VectorizedCollabTrainer if engine == "vec"
           else collab.CollabTrainer)
    loose = {"policy": policy, "participation": participation,
             "clock": clock, "download_clock": download_clock, "mesh": mesh,
             "arrivals": arrivals}
    loose = {k: v for k, v in loose.items() if v is not None}
    if fleet is None:
        fleet = FleetConfig(**loose)
    elif loose:
        raise ValueError(
            f"pass fleet=FleetConfig(...) OR loose kwargs, not both; got "
            f"fleet and {sorted(loose)}")
    return cls(specs, params, parts, test, ccfg, tcfg, seed=seed,
               fleet=fleet, telemetry=telemetry)


def run_mode(mode: str, n_clients: int, rounds: int = None, *,
             lambda_kd: float = 10.0, lambda_disc: float = 1.0,
             seed: int = 0, width: int = 1, engine: str = "vec"):
    rounds = rounds or ROUNDS
    tr = make_trainer(mode, n_clients, lambda_kd=lambda_kd,
                      lambda_disc=lambda_disc, seed=seed, width=width,
                      engine=engine)
    tr.run(rounds)
    return tr


def timeit(fn, *args, iters=10, warmup=2) -> float:
    """-> microseconds per call (post-jit, blocked)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6
