"""Every cell, configuration and metric is found by name, and
BENCHMARK.json keeps to its schema."""
import os
import re

import benchtest_util  # noqa: F401
from benchlib import cells

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_configs_are_found_by_name():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        cfg, mod = cells.config(c["name"])
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for attr in ("Cell", "Data", "Reference", "compare_summaries"):
            assert hasattr(mod, attr), (c["name"], attr)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_workloads_are_found_by_name():
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        traffic = cells.workload(w["name"])
        assert traffic["config"] == w["config"]
        assert set(traffic) == {"config", "traffic", "limits"}
        assert cells.cell_entry(BENCH, w["name"]) is w


def test_metrics_are_found_by_name_and_reported_by_every_cell():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert callable(cells.metric(m["name"]).read)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        got = [m["name"] for m in cells.metrics_of(BENCH, w["name"], False)]
        assert "setup_s" in got and len(got) >= 2
        assert cells.metrics_of(BENCH, w["name"], True)


def test_an_unknown_name_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        cells.cell_entry(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        cells.metric("no_such_metric")
    with pytest.raises(FileNotFoundError):
        cells.workload("no-such-cell")


def test_a_staged_traffic_file_resolves_as_a_one_chip_cell():
    import json
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"] = []
    for name in ("fleet-lenet5-n256", "lm-xlstm125m-4x2048"):
        entry = cells.staged_entry(bench, name)
        assert entry["chips"] == 1 and entry["traffic"] == name
        assert entry["config"] == cells.workload(name)["config"]
    w = BENCH["workloads"][0]
    assert cells.staged_entry(BENCH, w["name"]) is w
    import pytest
    with pytest.raises(FileNotFoundError):
        cells.staged_entry(bench, "no-such-cell")
