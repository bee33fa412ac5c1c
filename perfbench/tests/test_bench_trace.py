"""The reduction from a profiler trace to per-layer numbers."""
import os

import pytest

import benchtest_util  # noqa: F401
from benchlib import trace as trace_lib
from benchlib.trace import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    dev0 = [Op("conv.1", 0, 10, "jit(round_core)/jit(main)/update/vmap()/conv",
               "jit_round_core"),
            Op("fusion.2", 5, 10, "jit(round_core)/commit/scatter",
               "jit_round_core"),
            Op("fusion.3", 20, 10, "", "jit_hits"),
            Op("all-reduce.4", 40, 10, "jit(round_core)/exchange/x",
               "jit_round_core"),
            Op("copy.5", 95, 15, "jit(round_core)/updates/y", "jit_other")]
    dev1 = [Op("conv.1", 0, 50, "jit(round_core)/update/conv",
               "jit_round_core")]
    return Trace(window_ns=(0, 100), steps=2,
                 ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1})


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _trace()
    # [0,15) + [20,30) + [40,50) + [95,100): overlaps counted once, the
    # op that runs past the window clipped
    assert t.busy_s("/device:TPU:0") == pytest.approx(40e-9)
    assert t.busy_s("/device:TPU:1") == pytest.approx(50e-9)
    assert t.mean_busy_s() == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_scope_module_and_collective_times():
    t = _trace()
    # "update" matches whole path elements only: not "updates"
    assert t.scope_s(["update"]) == pytest.approx((10 + 50) / 2 * 1e-9)
    assert t.scope_s(["commit", "exchange"]) == pytest.approx(20 / 2 * 1e-9)
    assert t.module_s(r"\bjit_hits\b") == pytest.approx(10 / 2 * 1e-9)
    assert t.collective_s() == pytest.approx(10 / 2 * 1e-9)


def test_breakdown_lists_ops_and_named_gaps():
    t = _trace()
    top = t.top_ops(2)
    assert top[0][0] == "jit(round_core)/update/conv"
    gaps = t.idle_gaps([("run_round", 10, 60), ("eval", 28, 35)], 3)
    # gaps on the first device: [15,20), [30,40), [50,95)
    assert [g[1] for g in gaps] == pytest.approx([45e-9, 10e-9, 5e-9])
    assert [g[0] for g in gaps] == ["host", "eval", "run_round"]


def test_load_reads_the_step_spans_of_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with trace_lib.step_span(i):
                f(x).block_until_ready()
    t, spans = trace_lib.load(str(tmp_path))
    assert t.steps == 3
    assert t.window_s > 0
    assert spans


def test_a_recorded_chip_trace_reduces_to_its_layers(tmp_path):
    """One round of `fleet-lenet5-n256`, traced on a TPU v5 lite and cut
    down to that round's device ops and host step span."""
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "fleet_round.xplane.pb"),
                d / "host.xplane.pb")
    t, _ = trace_lib.load(str(tmp_path))
    assert t.steps == 1 and list(t.ops) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.103691166, rel=1e-6)
    assert t.mean_busy_s() == pytest.approx(0.086313306, rel=1e-6)
    assert t.scope_s(["update"]) == pytest.approx(0.070221273, rel=1e-6)
    assert t.scope_s(["teacher_read", "upload", "exchange", "commit"]) == \
        pytest.approx(0.006908976, rel=1e-6)
    assert t.module_s(r"\bjit_hits\b") == pytest.approx(0.008045135,
                                                        rel=1e-6)
    # one chip: no collectives; the while loop's own event is not counted
    assert t.collective_s() == 0.0
    assert not any(o.name.startswith("while") for o in t.ops["/device:TPU:0"])
