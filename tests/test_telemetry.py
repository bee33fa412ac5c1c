"""Fleet telemetry (src/repro/obs): in-jit round metrics, trace spans,
sinks and the report CLI.

The tentpole invariant: with telemetry on, BOTH engines emit a
`RoundTelemetry` every round whose integer leaves (ring occupancy/fill,
owner diversity, staleness and commit-lag histograms, pending depth,
stale reads) agree BIT-FOR-BIT across every relay policy × fleet clocking
(sync, event-ordered upload lag, download lag), because they reduce the
same exactly-matched ring/event bookkeeping; float leaves (prototype
drift, per-bucket loss/grad-norm) match within the engines' usual vmap
tolerance. Plus: telemetry is free when off (no record entry, and the
step still compiles ONCE with it on — a static build flag, not a traced
branch), the JSONL sink + `python -m repro.obs.report` round-trip, and
the Chrome trace the recorder writes is valid trace-event JSON.

The full policy × clocking cross product runs under the `slow` marker;
tier-1 runs a diagonal (same convention as test_download_lag).
"""
import json

import jax
import numpy as np
import pytest

from oracles import assert_telemetry_match, run_matched
from repro import obs
from repro.obs import report
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

POLICIES = ["flat", "per_class", "staleness"]
# sync, event-ordered upload lag, download lag
CLOCKINGS = [(None, None), ("lognormal:2", None), (None, "lognormal:2")]


def _build(engine, policy="flat", clock=None, dl_clock=None, schedule=None,
           telemetry=True, n_clients=4, seed=0, hetero=False):
    x, y = synthetic.class_images(192, seed=0, noise=0.4)
    tx, ty = synthetic.class_images(96, seed=9, noise=0.4)
    parts = partition.uniform_split(x, y, n_clients, seed=1)
    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84,
                        lambda_kd=2.0)
    tcfg = TrainConfig(batch_size=16)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_clients)
    if hetero:
        specs = [SPEC if i % 2 == 0 else SPEC_B for i in range(n_clients)]
        params = [mlp.init_mlp(k, hidden=64 if i % 2 == 0 else 96)
                  for i, k in enumerate(keys)]
    else:
        specs = [SPEC] * n_clients
        params = [mlp.init_mlp(k) for k in keys]
    cls = (collab.CollabTrainer if engine == "seq"
           else vec_collab.VectorizedCollabTrainer)
    return cls(specs, params, parts, (tx, ty), ccfg, tcfg, seed=seed,
               telemetry=telemetry,
               fleet=FleetConfig(policy=policy, participation=schedule,
                                 clock=clock, download_clock=dl_clock))


# ---------------------------------------------------------------------------
# tentpole: telemetry agrees bit-for-bit across engines
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy,clocking", list(zip(POLICIES, CLOCKINGS)))
def test_telemetry_seq_vec_equivalence(policy, clocking):
    """Tier-1 diagonal of the policy × clocking matrix (full cross product
    under -m slow). run_matched pins the telemetry leaves every round."""
    clock, dl = clocking
    run_matched(_build("seq", policy, clock=clock, dl_clock=dl),
                _build("vec", policy, clock=clock, dl_clock=dl))


@pytest.mark.slow
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("clocking", CLOCKINGS)
def test_telemetry_full_matrix(policy, clocking):
    clock, dl = clocking
    run_matched(_build("seq", policy, clock=clock, dl_clock=dl),
                _build("vec", policy, clock=clock, dl_clock=dl))


def test_telemetry_hetero_async():
    """Two spec-buckets + upload lag: the bucketed engine computes
    telemetry in its own jitted dispatch (no fused round step) and the
    bucket_loss/grad_norm leaves carry one entry per bucket."""
    seq = _build("seq", "staleness", clock="lognormal:2", hetero=True)
    vec = _build("vec", "staleness", clock="lognormal:2", hetero=True)
    run_matched(seq, vec)
    t = vec.history[-1]["telemetry"]
    assert len(t["bucket_loss"]) == 2 == len(t["bucket_grad_norm"])


def test_telemetry_partial_participation():
    """Absent clients are zeroed out of the bucket means and never count
    as stale reads; commit_hist still accounts every commit."""
    seq = _build("seq", "flat", schedule="uniform_k:2",
                 dl_clock="lognormal:2")
    vec = _build("vec", "flat", schedule="uniform_k:2",
                 dl_clock="lognormal:2")
    run_matched(seq, vec)
    for rec in vec.history:
        t = rec["telemetry"]
        assert sum(t["commit_hist"]) == len(rec["commits"])
        assert t["stale_reads"] <= len(rec["participants"])


# ---------------------------------------------------------------------------
# free when off, one compile when on
# ---------------------------------------------------------------------------
def test_telemetry_off_no_record():
    vec = _build("vec", telemetry=None)
    rec = vec.run_round()
    assert "telemetry" not in rec
    seq = _build("seq", telemetry=False)
    assert "telemetry" not in seq.run_round()


def test_telemetry_kwarg_validated():
    with pytest.raises(TypeError):
        _build("vec", telemetry="yes")


@pytest.mark.parametrize("clock", [None, "lognormal:2"])
def test_telemetry_compile_once(clock):
    """The telemetry flag is a STATIC build choice: with it on, the round
    step still traces exactly once across rounds (sync and async)."""
    vec = _build("vec", clock=clock)
    vec.run(3)
    assert vec._round_step._cache_size() == 1


def test_telemetry_sanity_sync():
    """Shape/semantics floor for one engine: sync fleets pend nothing,
    read nothing stale, and commit exactly the present set at lag 0."""
    vec = _build("vec")
    for _ in range(3):
        rec = vec.run_round()
        t = rec["telemetry"]
        assert t["pending_depth"] == 0 and t["stale_reads"] == 0
        assert t["commit_hist"][0] == len(rec["participants"])
        assert sum(t["commit_hist"][1:]) == 0
        assert t["occupancy"] >= sum(1 for _ in rec["commits"])
        assert len(t["fill"]) == 10
        assert len(t["stale_hist"]) == obs.STALE_BINS
        assert np.isfinite(t["proto_drift"])
    json.dumps(rec["telemetry"])  # JSON-safe host types


# ---------------------------------------------------------------------------
# sinks, report, trace
# ---------------------------------------------------------------------------
def test_jsonl_sink_and_report(tmp_path):
    path = tmp_path / "run.jsonl"
    cfg = obs.TelemetryConfig(jsonl=str(path))
    vec = _build("vec", clock="lognormal:2", telemetry=cfg)
    vec.run(3)
    records = obs.read_jsonl(str(path))
    assert len(records) == 3
    assert_telemetry_match(records[-1]["telemetry"],
                           vec.history[-1]["telemetry"])
    out = report.render(records)
    assert "run report: 3 rounds" in out
    assert "commit-lag histogram" in out
    assert "staleness histogram" in out
    assert "comm: up" in out
    # the CLI renders the same file end-to-end
    assert report.main([str(path), "--last", "2"]) == 0


def test_report_degrades_without_telemetry(tmp_path):
    """Sink on, metrics off: the report falls back to accuracy/comm."""
    path = tmp_path / "run.jsonl"
    cfg = obs.TelemetryConfig(metrics=False, jsonl=str(path))
    vec = _build("vec", telemetry=cfg)
    vec.run_round()
    out = report.render(obs.read_jsonl(str(path)))
    assert "run report: 1 rounds" in out
    assert "commit-lag histogram" not in out


def test_trace_chrome_json(tmp_path, monkeypatch):
    """The span store's recording, written once at the end of `run()`, is
    valid Chrome trace-event JSON (Perfetto's "Open trace file" format):
    complete "X" events with µs timestamps, covering each engine's round
    phases under a `run_round` span."""
    writes = []
    write = obs.TraceRecorder.write
    monkeypatch.setattr(obs.TraceRecorder, "write",
                        lambda rec, p: writes.append(p) or write(rec, p))
    path = tmp_path / "trace.json"
    seq = _build("seq", clock="lognormal:2",
                 telemetry=obs.TelemetryConfig(trace=str(path)))
    seq.run(2)
    events = json.loads(path.read_text())["traceEvents"]
    assert events
    names = {e["name"] for e in events}
    assert {"run_round", "teacher_read", "update", "upload", "commit",
            "eval"} <= names
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
        assert e["args"]["round"] in (0, 1)

    vpath = tmp_path / "vtrace.json"
    vec = _build("vec", telemetry=obs.TelemetryConfig(trace=str(vpath)))
    vec.run(2)
    assert writes == [str(path), str(vpath)]  # once a run, not every round
    vevents = json.loads(vpath.read_text())["traceEvents"]
    assert {"run_round", "inputs", "round_step", "fetch", "records",
            "eval", "eval_fetch", "log"} <= {e["name"] for e in vevents}
    assert {e["args"]["round"] for e in vevents} == {0, 1}
