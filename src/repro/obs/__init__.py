"""Fleet observability: in-jit round metrics, phase trace spans, sinks.

Three tiers, one opt-in surface:

  1. `repro.obs.metrics` — a fixed-shape `RoundTelemetry` pytree computed
     INSIDE the engines' jitted round steps (ring occupancy/fill, owner
     diversity, staleness and commit-lag histograms, pending depth,
     prototype drift, per-bucket loss/grad-norm), REPLICATED on a mesh
     and oracle-checked bit-for-bit between engines;
  2. `repro.obs.trace` — one process-wide store of host spans and
     counters at the round's phase boundaries, off by default: each span
     is also a jax.profiler annotation, stamped on the clock the device
     trace uses, and a recording is written once as Chrome trace-event
     JSON (open in Perfetto);
  3. `repro.obs.sink` / `repro.obs.report` — a JSONL per-round writer and
     the `python -m repro.obs.report` CLI that renders a run summary.

Engines take `telemetry=` (True for in-jit metrics only, or a
`TelemetryConfig` to add the sink or a span recording); the default None
keeps every round step's traced program byte-identical to a
telemetry-free build. The span store is process-wide: `obs.trace.start()`
turns it on for every engine in the process (the chip benchmark does so
from its own files), and no jitted program changes with it on or off. An
engine's `run()` with `TelemetryConfig.trace` records over that call alone
(`obs.trace.record`) and stops again the recording it started.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.obs import metrics, sink, trace  # noqa: F401  (re-exported tiers)
# repro.obs.report is deliberately NOT imported here: it is the
# `python -m repro.obs.report` CLI, and importing it from the package
# would make runpy warn about the double module identity.
from repro.obs.metrics import (  # noqa: F401
    STALE_BINS, RoundTelemetry, round_telemetry, shard_summary, to_record)
from repro.obs.sink import JsonlWriter, read_jsonl  # noqa: F401
from repro.obs.trace import NULL_SPAN, TraceRecorder  # noqa: F401


@dataclass(frozen=True)
class TelemetryConfig:
    """What to observe and where it goes.

    metrics: compute the in-jit RoundTelemetry each round (adds a
      `telemetry` entry to every round record). jsonl: stream each round
      record to this JSONL path. trace: record the process-wide span
      store over each `run()` (`obs.trace.record`) and write the recording
      to this Chrome trace-event JSON path once, at its end."""
    metrics: bool = True
    jsonl: Optional[str] = None
    trace: Optional[str] = None


def resolve(telemetry) -> Optional[TelemetryConfig]:
    """The engines' `telemetry=` kwarg: None/False -> off (no config),
    True -> in-jit metrics only, or a TelemetryConfig verbatim."""
    if telemetry is None or telemetry is False:
        return None
    if telemetry is True:
        return TelemetryConfig()
    if isinstance(telemetry, TelemetryConfig):
        return telemetry
    raise TypeError(
        f"telemetry= expects None, bool or obs.TelemetryConfig; got "
        f"{type(telemetry).__name__}")
