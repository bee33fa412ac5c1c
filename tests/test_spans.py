"""The span store (src/repro/obs/trace.py) inside the fleet driver.

With the store on, every round of `VectorizedCollabTrainer.run_round`
records one fixed span tree under a root `run_round`, all its spans sharing
the round's index, and stamps on the root how much each counter grew:
`host_syncs` (one per array pulled to the host) and `traces` (one per trace
of a jitted program, so a steady round adds none). With the store
off nothing is recorded and the jitted programs are the same text. The
store's clock is the profiler's, less one constant: the benchmark's helper
(`perfbench/benchlib/spans.py`) maps it onto a device trace with one
offset.
"""
import os
import sys

import jax
import pytest

from repro import obs
from repro.core import client as client_lib, vec_collab
from repro.data import partition, synthetic
from repro.models import mlp
from repro.types import CollabConfig, FleetConfig, TrainConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench"))
from benchlib import spans as bench_spans, trace as trace_lib  # noqa: E402

SPEC = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
SPEC_B = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))

FUSED = ["inputs", "round_step", "fetch", "records", "eval", "log"]
SPAN_NAMES = {"run_round", "eval_fetch", *FUSED}


@pytest.fixture(autouse=True)
def store_off():
    """The store is process-wide: every test starts and ends with it off."""
    obs.trace.stop()
    yield
    obs.trace.stop()


def _fleet(clock=None, hetero=False, n_clients=4):
    x, y = synthetic.class_images(192, seed=0, noise=0.4)
    tx, ty = synthetic.class_images(96, seed=9, noise=0.4)
    parts = partition.uniform_split(x, y, n_clients, seed=1)
    keys = jax.random.split(jax.random.PRNGKey(0), n_clients)
    specs = [SPEC_B if hetero and i % 2 else SPEC for i in range(n_clients)]
    params = [mlp.init_mlp(k, hidden=96 if hetero and i % 2 else 64)
              for i, k in enumerate(keys)]
    return vec_collab.VectorizedCollabTrainer(
        specs, params, parts, (tx, ty),
        CollabConfig(mode="cors", num_classes=10, d_feature=84,
                     lambda_kd=2.0),
        TrainConfig(batch_size=16), seed=0,
        fleet=FleetConfig(policy="flat", clock=clock))


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


@pytest.mark.parametrize("case", ["sync", "async", "hetero"])
def test_round_span_tree_and_counters(case):
    vec = _fleet(clock="lognormal:2" if case == "async" else None,
                 hetero=case == "hetero")
    buckets = 2 if case == "hetero" else 1
    obs.trace.start()
    recs = [vec.run_round() for _ in range(2)]
    spans = obs.trace.spans()

    roots = [s for s in spans if s.parent == -1]
    assert [(s.name, s.round) for s in roots] == [("run_round", 0),
                                                 ("run_round", 1)]
    leaves = len(recs[0]["metrics"][0])
    for root in roots:
        kids = _children(spans, root)
        # every fleet, mixed ones included, runs one round step
        assert [s.name for s in kids] == FUSED
        evals = _children(spans, kids[-2])
        assert [s.name for s in evals] == ["eval_fetch"] * buckets
        tree = [root] + kids + evals
        # every span of the round: only these, all with the round's index
        assert sorted(s.id for s in spans if s.round == root.round) == \
            sorted(s.id for s in tree)
        by_id = {s.id: s for s in spans}
        for s in kids + evals:
            p = by_id[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
        # one pull per metrics leaf and bucket, one per bucket's eval
        assert root.args["host_syncs"] == buckets * (leaves + 1)
    assert not [s for s in spans if s.name == "bucket_steps"]
    # round 0 traces the round step once and each bucket's eval once;
    # round 1, same shapes, traces nothing
    assert roots[0].args["traces"] == 1 + buckets
    assert "traces" not in roots[1].args
    assert obs.trace.counters() == {"traces": 1 + buckets,
                                    "host_syncs": 2 * buckets * (leaves + 1)}


def test_run_records_itself_alone_and_stops_what_it_started(tmp_path):
    """`TelemetryConfig.trace` records over `run()` and stops the store
    after it, so an engine built later records nothing; a recording that
    was on already stays on."""
    path = tmp_path / "trace.json"
    vec = _fleet()
    vec.telemetry = obs.TelemetryConfig(trace=str(path))
    vec.run(1)
    assert path.exists() and not obs.trace.recording()
    later = _fleet()
    later.run(1)
    later.run_round()
    assert obs.trace.spans() == [] and obs.trace.counters() == {}

    rec = obs.trace.start()
    vec.run(1)
    assert obs.trace.recording() and obs.trace.stop() is rec
    assert [s.round for s in rec.spans() if s.name == "run_round"] == [1]


def _lowered_texts():
    """Lowered text of a fresh fleet's round step and `jit_hits`, with the
    arguments of its first round."""
    vec = _fleet()
    (bucket,) = vec.buckets
    step, hits = vec._round_step, bucket.eval_fn
    calls = {}

    def spy(name, fn):
        def call(*args):
            calls[name] = args
            return fn(*args)
        return call

    vec._round_step, bucket.eval_fn = spy("step", step), spy("hits", hits)
    vec.run_round()
    return [fn.lower(*calls[name]).as_text()
            for name, fn in (("step", step), ("hits", hits))]


def test_store_off_records_nothing_and_programs_do_not_change():
    off = _lowered_texts()
    assert obs.trace.spans() == [] and obs.trace.counters() == {}
    assert obs.trace.span("run_round") is obs.NULL_SPAN
    obs.trace.start()
    on = _lowered_texts()
    assert obs.trace.counters()["traces"] >= 2
    assert on == off
    assert "jit_round_core" in off[0] and "jit_hits" in off[1]


def test_store_clock_is_the_profilers_less_one_constant(tmp_path):
    vec = _fleet()
    obs.trace.start()
    vec.run_round()                       # compiles outside the capture
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            with trace_lib.step_span(i):
                vec.run_round()
                jax.block_until_ready(vec.params)
    trace, host = trace_lib.load(str(tmp_path))
    assert trace.steps == 2
    stored = [s for s in obs.trace.spans() if s.round >= 1]
    pairs = []
    for name in SPAN_NAMES:
        xs = sorted((a, b) for n, a, b in host if n == name)
        ours = sorted((s.start_ns, s.end_ns, s.id) for s in stored
                      if s.name == name)
        assert len(xs) == len(ours) > 0, name
        pairs += [(x, o) for x, o in zip(xs, ours)]
    diffs = [x[k] - o[k] for x, o in pairs for k in (0, 1)]
    assert max(diffs) - min(diffs) < 50e3

    # the benchmark's alignment, anchored on the first window round,
    # reproduces the profiler's times
    mapped = {s.id: s for s in bench_spans.align(trace, [
        bench_spans.Span(*s) for s in obs.trace.spans()])}
    assert len(mapped) == len(stored)
    for x, o in pairs:
        m = mapped[o[2]]
        assert abs(m.start_ns - x[0]) < 200e3
        assert abs(m.end_ns - x[1]) < 200e3

