"""geomesa_tpu.telemetry — per-query span tracing, flight recorder and
live metrics export for the serve path (docs/OBSERVABILITY.md).

Pieces:

- `trace.py`: the span core. `TRACER.span("phase")` context managers at
  every serve/plan/engine seam; <2µs per live span, a shared no-op when
  tracing is off or the thread has no scoped trace.
- `recorder.py`: `RECORDER`, a bounded ring buffer of the last N
  completed query traces plus breaker/quarantine/fault events, dumpable
  on demand or automatically on un-typed dispatcher errors.
- `export.py`: Chrome/Perfetto trace JSON, JSON-lines, and the
  `MetricsServer` behind `gmtpu serve --metrics-port` (`/metrics`,
  `/healthz`, `/debug/traces`, `/debug/stats`, `/debug/gap`).
- `gap.py`: the dispatch-gap report (`gmtpu trace --gap`) — host-gap vs
  kernel-time attribution aggregated from spans, the evidence ROADMAP
  item 2's pipelining work starts from.
- `slo.py`: declared objectives + sliding-window error-budget burn
  (`/debug/slo`, `slo.burn_rate`/`slo.budget_remaining` gauges, the
  degradation ladder's burn-rate input).
- `prof.py`: the always-on continuous profiler (`gmtpu prof`,
  `/debug/prof`) — reservoir-sampled per-phase/per-kernel/per-shard
  distributions folded from every recorded trace at bounded cost.
"""

from geomesa_tpu.telemetry.export import (MetricsServer, from_perfetto,
                                          to_perfetto, write_jsonl)
from geomesa_tpu.telemetry.gap import gap_report, render_gap
from geomesa_tpu.telemetry.prof import (PROFILER, ContinuousProfiler,
                                        render_prof)
from geomesa_tpu.telemetry.recorder import RECORDER, FlightRecorder
from geomesa_tpu.telemetry.slo import SloEngine, SloSpec, render_slo
from geomesa_tpu.telemetry.trace import NOOP_SPAN, Span, Trace, Tracer, TRACER

__all__ = [
    "TRACER", "Tracer", "Trace", "Span", "NOOP_SPAN",
    "RECORDER", "FlightRecorder",
    "MetricsServer", "to_perfetto", "from_perfetto", "write_jsonl",
    "gap_report", "render_gap",
    "SloEngine", "SloSpec", "render_slo",
    "PROFILER", "ContinuousProfiler", "render_prof",
]
