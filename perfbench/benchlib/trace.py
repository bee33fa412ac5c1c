"""Capture a profiler trace of a steady window and reduce it.

A traced run wraps each timed step of its window in a host span named
`STEP_SPAN` (`step_span`) inside `jax.profiler.trace`.
`load` reads the `.xplane.pb` the profiler writes (`benchlib.xplane`) and
keeps what the per-layer metrics need: the window (first step span's start
to the last one's end, on the trace's clock), and every device operation
with its start, duration, HLO module and name stack (its metadata's
`tf_op`: the program's `jax.named_scope` labels). `Trace` then answers: busy time (the union of
operation intervals inside the window), time under named scopes, time in
programs whose module name matches, and time in collectives.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence

from benchlib import xplane

STEP_SPAN = "bench_step"
# XLA's collective instructions, as their names appear in device op events.
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter")
# Device-op lines, in order of preference.
_OP_LINES = ("XLA Ops", "Ops")
_MODULE_LINES = ("XLA Modules", "Modules")
# Control-flow ops span the ops of their bodies, which are events of their
# own; counting both would count the body twice.
_CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""
    module: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """A reduced trace: the window, the number of steps in it, and the
    device operations of each device plane."""
    window_ns: Sequence[float]
    steps: int
    ops: Dict[str, List[Op]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def _clipped(self, ops: Iterable[Op]):
        t0, t1 = self.window_ns
        for op in ops:
            a, b = max(op.start_ns, t0), min(op.end_ns, t1)
            if b > a:
                yield a, b

    def busy_s(self, device: str) -> float:
        """Seconds of the window in which any operation ran on `device`."""
        total, end = 0.0, None
        for a, b in sorted(self._clipped(self.ops[device])):
            if end is None or a > end:
                total += b - a
                end = b
            elif b > end:
                total += b - end
                end = b
        return total * 1e-9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the device planes."""
        if not self.ops:
            return 0.0
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def _sum(self, pred) -> float:
        """Seconds of ops matching `pred`, summed over devices and averaged
        over them, inside the window."""
        if not self.ops:
            return 0.0
        tot = 0.0
        for ops in self.ops.values():
            tot += sum(b - a for a, b in self._clipped(o for o in ops
                                                       if pred(o)))
        return tot * 1e-9 / len(self.ops)

    def scope_s(self, scopes: Sequence[str]) -> float:
        """Device seconds of ops whose name stack holds one of `scopes` as
        a whole path element."""
        want = set(scopes)
        return self._sum(lambda o: bool(want.intersection(
            _path_elements(o.scope))))

    def module_s(self, pattern: str) -> float:
        """Device seconds of ops in HLO modules whose name matches the
        regular expression `pattern`."""
        rx = re.compile(pattern)
        return self._sum(lambda o: bool(rx.search(o.module)))

    def collective_s(self) -> float:
        return self._sum(lambda o: bool(COLLECTIVE_RE.search(o.name)))

    def top_ops(self, n: int = 10):
        """[[name, seconds], ...] of the ops that took most device time
        (summed over devices and averaged over them)."""
        acc: Dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                key = o.scope or o.name
                acc[key] = acc.get(key, 0.0) + o.dur_ns
        k = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_gaps(self, host_spans: Sequence, n: int = 10):
        """The longest gaps between device ops on the first device, named
        by the innermost host span that covers the middle of each gap."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        iv = sorted(self._clipped(self.ops[dev]))
        gaps, end = [], self.window_ns[0]
        for a, b in iv:
            if a > end:
                gaps.append((end, a))
            end = max(end, b)
        if self.window_ns[1] > end:
            gaps.append((end, self.window_ns[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            cover = [s for s in host_spans if s[1] <= mid <= s[2]]
            name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
                else "host"
            out.append([name, (b - a) * 1e-9])
        return out


def _path_elements(scope: str) -> List[str]:
    return [p.split("(")[0] for p in scope.split("/")]


def step_span(i: int):
    import jax
    return jax.profiler.TraceAnnotation(STEP_SPAN, step=i)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(logdir: str) -> "tuple[Trace, list]":
    """Reduce the trace under `logdir`. Returns (Trace, host_spans) where
    host_spans are (name, start_ns, end_ns) of the host's trace events,
    used to name idle gaps."""
    space = xplane.read(find_xplane(logdir))
    steps: List[tuple] = []
    host_spans: List[tuple] = []
    ops: Dict[str, List[Op]] = {}
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        lines = {line.name: line for line in plane.lines}

        def events(line):
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps * 1e-3
                yield meta[ev.metadata_id], start, ev.duration_ps * 1e-3

        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for md, start, dur in events(line):
                    if md.name == STEP_SPAN:
                        steps.append((start, start + dur))
                    elif dur > 0:
                        host_spans.append((md.name, start, start + dur))
        elif plane.name.startswith("/device:"):
            op_line = next((lines[n] for n in _OP_LINES if n in lines), None)
            if op_line is None:
                continue
            mod_line = next((lines[n] for n in _MODULE_LINES if n in lines),
                            None)
            modules = sorted((start, start + dur, md.name) for md, start, dur
                             in (events(mod_line) if mod_line else ()))
            kinds = {}      # metadata id -> (name, scope), None to skip
            for k, md in meta.items():
                st = xplane.stat_values(md.stats, stat_names)
                kinds[k] = None if st.get("hlo_category") in _CONTAINERS \
                    else (md.display_name or md.name, str(st.get("tf_op", "")))
            dev_ops = []
            for ev in op_line.events:
                kind = kinds[ev.metadata_id]
                if kind is None:
                    continue
                start = op_line.timestamp_ns + ev.offset_ps * 1e-3
                dev_ops.append(Op(kind[0], start, ev.duration_ps * 1e-3,
                                  kind[1], _module_at(modules, start)))
            if dev_ops:
                ops[plane.name] = dev_ops
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} spans in the trace")
    window = (min(s for s, _ in steps), max(e for _, e in steps))
    return Trace(window_ns=window, steps=len(steps), ops=ops), host_spans


def _module_at(modules, t) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""

