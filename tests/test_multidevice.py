"""Multi-device placement-path validation (relay/placement.py).

The `FleetConfig.mesh` path in core/vec_collab.py is exercised on a
1-device mesh by the in-process suites, where any collective is an
identity. This forces FOUR host CPU devices in a subprocess (XLA_FLAGS
must be set before jax import, hence the subprocess) and runs the
seq/vec oracle harness (tests/oracles.py) against the placement-aware
round step for every composition the mesh used to reject: synchronous,
async (client-sharded pending buffer), download lag (replicated history
ring), static-k compaction (k not divisible by the device count — GSPMD
pads the in-jit block) and bucketed heterogeneous fleets (bucket sizes
not divisible by the device count — those stacks fall back to
replicated). Exact ring bookkeeping, commit lists and ledgers;
float-tolerant observations; compile-once on the round step.
"""
import os
import subprocess
import sys

_SCRIPT = r"""
import jax
import numpy as np

assert jax.device_count() == 4, jax.devices()

from oracles import run_matched
from repro import sharding
from repro.core import client as client_lib, collab, vec_collab
from repro.data import partition, synthetic
from repro.models import mlp
from repro.types import CollabConfig, FleetConfig, TrainConfig

SPEC = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
SPEC_B = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
N = 8

def build(engine, mesh=None, policy=None, schedule=None, clock=None,
          download_clock=None, hetero=False, n=N, telemetry=None):
    x, y = synthetic.class_images(192, seed=0, noise=0.4)
    tx, ty = synthetic.class_images(96, seed=9, noise=0.4)
    parts = partition.uniform_split(x, y, n, seed=1)
    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84,
                        lambda_kd=2.0, lambda_disc=1.0)
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    if hetero:
        # 5-vs-3 split: bucket sizes NOT divisible by the 4 devices
        specs = [SPEC if i % 3 else SPEC_B for i in range(n)]
        params = [mlp.init_mlp(k, hidden=64 if i % 3 else 96)
                  for i, k in enumerate(keys)]
    else:
        specs = [SPEC] * n
        params = [mlp.init_mlp(k) for k in keys]
    cls = (collab.CollabTrainer if engine == "seq"
           else vec_collab.VectorizedCollabTrainer)
    return cls(specs, params, parts, (tx, ty), ccfg,
               TrainConfig(batch_size=16), seed=0, telemetry=telemetry,
               fleet=FleetConfig(mesh=mesh, policy=policy,
                                 participation=schedule, clock=clock,
                                 download_clock=download_clock))

mesh = sharding.client_mesh(4)          # 2 clients per device

# state at rest cannot hold an uneven sharding: the TOTAL client axis
# must divide the mesh (uneven hetero buckets are the sanctioned case)
try:
    build("vec", mesh=mesh, n=6)
except ValueError as e:
    assert "must divide" in str(e), e
else:
    raise SystemExit("N=6 on a 4-device mesh should be rejected")
print("UNEVEN_GUARD_OK")

# sync: mesh path vs the sequential oracle, compile-once
vec = build("vec", mesh=mesh)
run_matched(build("seq"), vec, rounds=2)
assert vec._round_step._cache_size() == 1
print("SYNC_OK")

# async: client-sharded pending buffer, event-ordered commits
vec = build("vec", mesh=mesh, policy="staleness", clock="lognormal:2")
run_matched(build("seq", policy="staleness", clock="lognormal:2"), vec,
            rounds=3)
assert vec._round_step._cache_size() == 1
print("ASYNC_OK")

# download lag: replicated history ring, local stale gathers
vec = build("vec", mesh=mesh, policy="per_class",
            download_clock="lognormal:2")
run_matched(build("seq", policy="per_class", download_clock="lognormal:2"),
            vec, rounds=3)
assert vec._round_step._cache_size() == 1
print("DOWNLOAD_OK")

# static-k compaction: k=3 participants on 4 devices (GSPMD pads)
vec = build("vec", mesh=mesh, schedule="uniform_k:3")
assert vec._k_active == 3
run_matched(build("seq", schedule="uniform_k:3"), vec, rounds=2)
print("STATICK_OK")

# hetero buckets (5 + 3 clients) sharing one relay over the mesh
vec = build("vec", mesh=mesh, hetero=True)
assert vec.hetero and len(vec.buckets) == 2
run_matched(build("seq", hetero=True), vec, rounds=2)
assert vec._round_step._cache_size() == 1
print("HETERO_OK")

# telemetry on the mesh: every RoundTelemetry leaf is declared REPLICATED
# (obs.metrics.out_spec) and run_matched pins its integer leaves against
# the oracle bit-for-bit; the extra output must not cost a recompile
vec = build("vec", mesh=mesh, policy="staleness", clock="lognormal:2",
            telemetry=True)
run_matched(build("seq", policy="staleness", clock="lognormal:2",
                  telemetry=True), vec, rounds=3)
assert vec._round_step._cache_size() == 1
t = vec.history[-1]["telemetry"]
assert "occupancy" in t and "commit_hist" in t
print("TELEMETRY_OK")

# async x download-lag x mesh in one run: the full composition
vec = build("vec", mesh=mesh, clock="lognormal:2",
            download_clock="lognormal:2")
run_matched(build("seq", clock="lognormal:2", download_clock="lognormal:2"),
            vec, rounds=3)
print("COMPOSED_OK")

print("MULTIDEVICE_OK")
"""


def test_placement_4_devices_matches_oracle():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + os.path.join(root, "tests") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    for marker in ("UNEVEN_GUARD_OK", "SYNC_OK", "ASYNC_OK", "DOWNLOAD_OK",
                   "STATICK_OK", "HETERO_OK", "TELEMETRY_OK",
                   "COMPOSED_OK", "MULTIDEVICE_OK"):
        assert marker in out.stdout, out.stdout
