"""The program's own host spans and counters, on the device trace's clock.

The program keeps one process-wide store of host spans and counters
(`repro.obs.trace`), off by default. A reader that needs it calls `start()`
as it is imported. Readers of per-layer metrics are loaded only in traced
runs, before set-up, so the store records the whole traced run and an
end-to-end run never records. A program without such a store (an older
commit) records nothing, and every reading here is then None.

The store stamps `time.monotonic_ns()`. The profiler writes host events on
that clock less a constant of its session. The traced window opens when the
first window step's `bench_step` span does, a few µs before the program
enters that step's `run_round`. So one offset, the first window round's
start less the window's start, maps the store onto the trace. As a check,
the last window round, once mapped, must end within `MAX_TAIL_NS` before
the window closes (and no later than `SLACK_NS` after it). If it does not,
every reading is None rather than a wrong number.

The window's rounds are the last `trace.steps` root `run_round` spans in the
store: after the window the run checks its result with the reference alone,
which opens no span. Idle time is read on the first device, as
`Trace.idle_gaps` reads it.
"""
from __future__ import annotations

from collections import namedtuple
from typing import List, Optional, Sequence, Tuple

ROUND = "run_round"
FETCHES = ("fetch", "eval_fetch")
MAX_TAIL_NS = 2e6
SLACK_NS = 5e4

Span = namedtuple("Span", "id parent name round start_ns end_ns args")


def start() -> bool:
    """Turn the program's span store on; -> whether the program has one."""
    from repro.obs import trace as obs_trace
    on = getattr(obs_trace, "start", None)
    if on is None:
        return False
    on()
    return True


def store() -> Optional[List[Span]]:
    """The program's recorded spans, or None where it has no store."""
    from repro.obs import trace as obs_trace
    get = getattr(obs_trace, "spans", None)
    return None if get is None else [Span(*s) for s in get()]


def align(trace, spans: Sequence[Span]) -> Optional[List[Span]]:
    """The window's rounds and every span under them, mapped onto the
    trace's clock; None if there are too few rounds or the check fails."""
    n = trace.steps
    rounds = [s for s in spans if s.name == ROUND and s.parent == -1]
    if n <= 0 or len(rounds) < n:
        return None
    rounds = rounds[-n:]
    offset = rounds[0].start_ns - trace.window_ns[0]
    tail = trace.window_ns[1] - (rounds[-1].end_ns - offset)
    if not -SLACK_NS <= tail <= MAX_TAIL_NS:
        return None
    keep = {r.id for r in rounds}
    out = []
    for s in sorted(spans, key=lambda s: s.id):
        if s.id in keep or s.parent in keep:
            keep.add(s.id)
            out.append(s._replace(start_ns=s.start_ns - offset,
                                  end_ns=s.end_ns - offset))
    return out


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def _clip(intervals, window) -> List[Tuple[float, float]]:
    t0, t1 = window
    return _union((max(a, t0), min(b, t1)) for a, b in intervals
                  if min(b, t1) > max(a, t0))


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _intersect(xs, ys) -> List[Tuple[float, float]]:
    """The intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_intervals(trace) -> Optional[List[Tuple[float, float]]]:
    """The window's intervals in which no operation ran on the first
    device; None without device operations."""
    if not trace.ops:
        return None
    dev = sorted(trace.ops)[0]
    busy = _clip(((o.start_ns, o.end_ns) for o in trace.ops[dev]),
                 trace.window_ns)
    gaps, end = [], trace.window_ns[0]
    for a, b in busy:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if trace.window_ns[1] > end:
        gaps.append((end, trace.window_ns[1]))
    return gaps


def _host(trace, mapped):
    """(host time in rounds, host time in pulls), each a sorted disjoint
    interval list clipped to the window."""
    rounds = _clip(((s.start_ns, s.end_ns) for s in mapped
                    if s.name == ROUND and s.parent == -1), trace.window_ns)
    pulls = _clip(((s.start_ns, s.end_ns) for s in mapped
                   if s.name in FETCHES), trace.window_ns)
    return rounds, pulls


def driver_ms(trace, spans) -> Optional[float]:
    """Host ms per round inside `run_round` and outside its pulls."""
    mapped = align(trace, spans)
    if mapped is None:
        return None
    rounds, pulls = _host(trace, mapped)
    busy = _length(rounds) - _length(_intersect(rounds, pulls))
    return busy / trace.steps * 1e-6


def idle_split_ms(trace, spans) -> Optional[Tuple[float, float, float]]:
    """Device idle ms per round while the host (a) is in `run_round` but
    not in a pull, (b) is in a pull, (c) is outside `run_round`."""
    mapped = align(trace, spans)
    idle = idle_intervals(trace)
    if mapped is None or idle is None:
        return None
    rounds, pulls = _host(trace, mapped)
    in_rounds = _length(_intersect(idle, rounds))
    in_pulls = _length(_intersect(idle, _intersect(pulls, rounds)))
    k = trace.steps * 1e6
    return ((in_rounds - in_pulls) / k, in_pulls / k,
            (_length(idle) - in_rounds) / k)


def counter(trace, spans, name: str) -> Optional[int]:
    """How much counter `name` grew over the window's rounds."""
    mapped = align(trace, spans)
    if mapped is None:
        return None
    return sum((s.args or {}).get(name, 0) for s in mapped
               if s.name == ROUND and s.parent == -1)


def reading(run, fn, *args):
    """`fn(trace, spans, *args)` of a traced run, or None where the run
    has no trace or the program no store."""
    spans = store()
    if run.trace is None or spans is None:
        return None
    return fn(run.trace, spans, *args)
