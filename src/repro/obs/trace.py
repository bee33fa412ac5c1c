"""Host spans and counters of the round phases: one process-wide store.

Tier 2 of the telemetry layer. The engines open a span at each phase
boundary of a round with `span(name)` and bump counters at the same
boundaries with `count(name, n)`. The store is off by default: while no
recording is active, `span` returns `NULL_SPAN` and `count` returns, each
after one global check. Nothing here ever enters a traced program, so
every jitted program is the same with the store on or off.

`start()` begins a recording for the whole process. Each span then

  - enters a `jax.profiler.TraceAnnotation(name)`, so it shows in any
    `jax.profiler.trace` capture beside the device's operations, and
  - keeps `Span(id, parent, name, round, start_ns, end_ns, args)` in a
    table indexed by span id, stamped with `time.monotonic_ns()` just
    inside the annotation. The profiler writes host events on that clock less one
    constant per capture, so a single offset maps the store onto a device
    trace.

A span's round is the one its caller gives, or else its parent's: all the
spans of one round share it. A root span (one with no parent) carries as
args how much each counter grew while it was open. Summing those over the
root spans of a window gives the window's counts, with no snapshot taken
at its edges.

Spans time the host. A jitted call returns once it is dispatched, so a
span around it ends then, and the wait for the device lands in the span
that pulls a result to the host. What the device did meanwhile is read
from the device trace, on the same clock.

`TraceRecorder.write(path)` dumps a recording once, as Chrome trace-event
JSON (open it in Perfetto, https://ui.perfetto.dev, or chrome://tracing).
`record(path)` wraps a block in a recording and writes it at the block's
end; the engines' `run()` does so when `TelemetryConfig.trace` names a
path, and stops the store again unless it was on already.

In-jit phase labels are separate and always on: the round steps wrap their
phases in `jax.named_scope`, which only names HLO metadata.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    """One recorded span. Times are `time.monotonic_ns()`; `parent` and
    `round` are -1 where there is none."""
    id: int
    parent: int
    name: str
    round: int
    start_ns: int
    end_ns: int
    args: Optional[dict]


class _NullSpan:
    """What `span` returns while nothing records."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_rec", "_name", "_round", "_args", "_ann", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, round, args: dict):
        self._rec, self._name, self._round, self._args = rec, name, round, args

    def __enter__(self):
        self._rec._open(self._round)
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ann.__exit__(exc_type, exc, tb)
        self._rec._close(self._name, self._t0, time.monotonic_ns(),
                         self._args)
        return False


class TraceRecorder:
    """One recording: spans in a list indexed by span id (a span's row is
    filled when it closes), counters, and the stack of open spans."""

    def __init__(self):
        self._rows: List[Optional[Span]] = []
        # open spans: (id, parent, round, counters at open for a root)
        self._stack: List[tuple] = []
        self._counters: Dict[str, int] = {}

    def count(self, name: str, n: int = 1) -> None:
        self._counters[name] = self._counters.get(name, 0) + n

    def _open(self, round) -> None:
        i = len(self._rows)
        self._rows.append(None)
        if self._stack:
            top = self._stack[-1]
            parent, inherited, base = top[0], top[2], None
        else:
            parent, inherited, base = -1, -1, dict(self._counters)
        self._stack.append((i, parent, inherited if round is None else round,
                            base))

    def _close(self, name, t0, t1, args) -> None:
        i, parent, round, base = self._stack.pop()
        if base is not None:
            grew = {k: v - base.get(k, 0) for k, v in self._counters.items()
                    if v != base.get(k, 0)}
            args = {**args, **grew}
        self._rows[i] = Span(i, parent, name, round, t0, t1, args or None)

    def spans(self) -> List[Span]:
        """The closed spans, in the order they opened."""
        return [s for s in self._rows if s is not None]

    def counters(self) -> Dict[str, int]:
        return dict(self._counters)

    def write(self, path: str) -> None:
        """Write the closed spans to `path` as Chrome trace-event JSON:
        complete ("X") events in µs from the first span's start, with the
        span's id, parent, round and args under "args"."""
        rows = self.spans()
        t0 = min((s.start_ns for s in rows), default=0)
        events = [{"name": s.name, "ph": "X", "pid": 1, "tid": 1,
                   "ts": (s.start_ns - t0) / 1e3,
                   "dur": (s.end_ns - s.start_ns) / 1e3,
                   "args": {"id": s.id, "parent": s.parent,
                            "round": s.round, **(s.args or {})}}
                  for s in rows]
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


_ACTIVE: Optional[TraceRecorder] = None


def start() -> TraceRecorder:
    """Start recording for the whole process, or return the recording
    that is already on."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = TraceRecorder()
    return _ACTIVE


def stop() -> Optional[TraceRecorder]:
    """Stop recording; -> the recording that was on, or None."""
    global _ACTIVE
    rec, _ACTIVE = _ACTIVE, None
    return rec


def recording() -> bool:
    """Whether a recording is on."""
    return _ACTIVE is not None


@contextlib.contextmanager
def record(path: Optional[str]):
    """Record the spans of the block and write the recording to `path`
    once, when the block ends. A recording that was on already stays on
    (and all of it is written); one this call started is stopped. With
    no path, the block runs as it is."""
    if path is None:
        yield
        return
    owner = _ACTIVE is None
    rec = start()
    try:
        yield
        rec.write(path)
    finally:
        if owner:
            stop()


def span(name: str, round: Optional[int] = None, **args):
    """A context manager around one phase: `NULL_SPAN` while nothing
    records."""
    rec = _ACTIVE
    if rec is None:
        return NULL_SPAN
    return _Span(rec, name, round, args)


def count(name: str, n: int = 1) -> None:
    """Add `n` to counter `name` while a recording is on."""
    rec = _ACTIVE
    if rec is not None:
        rec.count(name, n)


def spans() -> List[Span]:
    """The active recording's closed spans; [] while nothing records."""
    return _ACTIVE.spans() if _ACTIVE is not None else []


def counters() -> Dict[str, int]:
    """The active recording's counters; {} while nothing records."""
    return _ACTIVE.counters() if _ACTIVE is not None else {}
