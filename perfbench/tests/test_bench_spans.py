"""The fleet driver's readers: the program's spans, mapped onto the trace's
clock, against the device's idle time (`benchlib/spans.py`)."""
from types import SimpleNamespace

import pytest

import benchtest_util  # noqa: F401
from benchlib import cells, spans
from benchlib.spans import Span
from benchlib.trace import Op, Trace

# the store's clock runs this far ahead of the trace's
OFFSET = 7_000_000_000
READERS = ("driver_ms.fleet", "idle_driver_ms.fleet", "idle_fetch_ms.fleet",
           "host_syncs.fleet", "retraces.fleet")


def _trace(window_end=21000):
    dev0 = [Op("e", 0, 1200),           # runs into the window's start
            Op("a", 1600, 2400), Op("b", 8000, 500), Op("c", 10500, 1500),
            Op("d", 20500, 1500)]       # runs past the window's end
    dev1 = [Op("x", 1000, 20000)]       # idle time is read on the first
    return Trace(window_ns=(1000, window_end), steps=2,
                 ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1})


def _spans():
    """A set-up round that ends inside the window once mapped, then the two
    window rounds; the second pulls no metrics. Times as the trace sees
    them, shifted onto the store's clock."""
    rows = [(0, -1, "run_round", 0, 500, 1200, {"host_syncs": 7,
                                                 "traces": 2}),
            (1, 0, "fetch", 0, 600, 1100, None),
            (2, -1, "run_round", 1, 1000, 10000, {"host_syncs": 7,
                                                   "traces": 1}),
            (3, 2, "inputs", 1, 1000, 1500, None),
            (4, 2, "round_step", 1, 1500, 2000, None),
            (5, 2, "fetch", 1, 2000, 5000, None),
            (6, 2, "records", 1, 5000, 7000, None),
            (7, 2, "eval", 1, 7000, 9500, None),
            (8, 7, "eval_fetch", 1, 8000, 9000, None),
            (9, 2, "log", 1, 9500, 10000, None),
            (10, -1, "run_round", 2, 11000, 20000, {"host_syncs": 1}),
            (11, 10, "eval", 2, 16000, 19500, None),
            (12, 11, "eval_fetch", 2, 17000, 19000, None)]
    return [Span(i, p, n, r, a + OFFSET, b + OFFSET, args)
            for i, p, n, r, a, b, args in rows]


def test_alignment_maps_the_window_rounds_onto_the_trace():
    mapped = spans.align(_trace(), _spans())
    assert [s.id for s in mapped] == list(range(2, 13))
    assert (mapped[0].start_ns, mapped[0].end_ns) == (1000, 10000)


def test_idle_time_split_by_what_the_host_was_doing():
    # idle on the first device: [1200,1600) [4000,8000) [8500,10500)
    # [12000,20500) = 14900; in rounds 13900, of it in pulls 3500
    driver, fetch, outside = spans.idle_split_ms(_trace(), _spans())
    assert driver == pytest.approx((13900 - 3500) / 2 * 1e-6)
    assert fetch == pytest.approx(3500 / 2 * 1e-6)
    assert outside == pytest.approx(1000 / 2 * 1e-6)
    t = _trace()
    idle_ms = (t.window_s - t.busy_s("/device:TPU:0")) * 1e3 / t.steps
    assert driver + fetch + outside == pytest.approx(idle_ms)


def test_driver_time_and_counters():
    # rounds 18000 ns, of it in pulls 3000 + 1000 + 2000
    assert spans.driver_ms(_trace(), _spans()) == pytest.approx(
        (18000 - 6000) / 2 * 1e-6)
    assert spans.counter(_trace(), _spans(), "host_syncs") == 8
    assert spans.counter(_trace(), _spans(), "traces") == 1


def _late():
    """The last window round ends 0.1 ms after the window closes."""
    rows = _spans()
    rows[10] = rows[10]._replace(end_ns=rows[10].end_ns + 100_000)
    return rows


@pytest.mark.parametrize("trace,rows", [
    (_trace(window_end=10_000_000), _spans()),   # ends 10 ms before close
    (_trace(), _late()),                         # ends after the close
    (_trace(), _spans()[2:10]),                  # one window round short
    (_trace(), []),                              # nothing recorded
])
def test_a_failed_alignment_reads_nothing(trace, rows):
    assert spans.align(trace, rows) is None
    assert spans.driver_ms(trace, rows) is None
    assert spans.idle_split_ms(trace, rows) is None
    assert spans.counter(trace, rows, "traces") is None


def test_readers(monkeypatch):
    readers = {name: cells.metric(name) for name in READERS}
    from repro.obs import trace as obs_trace
    assert obs_trace.recording()     # started as they were imported
    monkeypatch.setattr(spans, "store", _spans)
    run = SimpleNamespace(trace=_trace())
    got = {name: mod.read(run) for name, mod in readers.items()}
    assert got == pytest.approx({
        "driver_ms.fleet": 6000e-6, "idle_driver_ms.fleet": 5200e-6,
        "idle_fetch_ms.fleet": 1750e-6, "host_syncs.fleet": 4.0,
        "retraces.fleet": 1.0})
    # an untraced run, or a program without a store, reads nothing
    assert all(m.read(SimpleNamespace(trace=None)) is None
               for m in readers.values())
    monkeypatch.setattr(spans, "store", lambda: None)
    assert all(m.read(run) is None for m in readers.values())
