"""A whole run's last line keeps to the result schema for every cell in
BENCHMARK.json, and a run without the chips the cell asks for prints no
result."""
import io
from contextlib import redirect_stderr, redirect_stdout

import pytest

import benchtest_util
from benchlib import cells

BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _names(cell, traced):
    return {m["name"] for m in cells.metrics_of(BENCH, cell, traced)}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line_reports_the_end_to_end_metrics(monkeypatch, cell):
    rc, line, err = benchtest_util.run_small(monkeypatch, cell, 2 ** 40 + 1)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == _names(cell, False)
    for m in cells.metrics_of(BENCH, cell, False):
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, c in line["checks"].items():
        assert set(c) == {"value", "limit"}
        assert f"check {name} " in err
    # the checks are the last lines of standard error
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_reports_per_layer_metrics(monkeypatch, cell):
    rc, line, _ = benchtest_util.run_small(monkeypatch, cell, 5, trace=1)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) <= _names(cell, True)
    # this host's trace has no device plane: only the cell's own model
    # FLOP/s share, which is read from the traced window's length
    mfu = {n for n in _names(cell, True) if n.startswith("mfu.")}
    assert mfu and mfu <= set(line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"


def test_no_tpu_means_no_result():
    run = benchtest_util.load_run_module()
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", "fleet-lenet5-n256", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert out.getvalue() == ""
    assert "TPU" in err.getvalue()
