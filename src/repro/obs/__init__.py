"""End-to-end observability for the serving and retrieval layers.

    trace       per-request span trees, bounded ring buffer, Chrome
                trace-event export (Perfetto-viewable)
    registry    labeled thread-safe metrics (counters / gauges /
                histograms) — the one sink every exporter scrapes
    exporters   Prometheus text exposition + stdlib HTTP endpoint +
                JSONL snapshot writer
    phase       host phases of a serving thread: a ``seismic.<name>``
                profiler annotation plus a latency histogram
    report      `python -m repro.obs.report` snapshot + slowest-trace
                tables

``Observability`` is the bundle a server takes: one registry, one
tracer, and the stage-sampling knob. Request/queue/launch spans are
recorded for EVERY request (cheap plain-python bookkeeping); the
stage-level children require the stage-by-stage pipeline, which
materializes inter-stage arrays and costs roughly one extra fused
launch of wall time, so they are recorded on every
``stage_sample_every``-th launch — sampled tracing keeps full
instrumentation inside the <5% p50 / <3% QPS overhead gate
(``benchmarks/obs_overhead.py``; the default cadence amortizes the
staged launch to well under 1% of throughput) while still producing a
complete request -> queue_wait -> launch -> stages -> refine-round
tree on a steady cadence. Set ``stage_sample_every=1`` to trace stages on every
launch (demos, debugging), ``0`` to disable stage detail entirely.
"""
from __future__ import annotations

import dataclasses

from repro.obs.exporters import (ObsHTTPServer, parse_prometheus_text,
                                 prometheus_text, start_exporter,
                                 write_jsonl_snapshot)
from repro.obs.phase import phase
from repro.obs.quality import (FUNNEL_STAGES, ShadowAuditor, per_query_recall,
                               recall_at_k, sample_stats, wilson_interval)
from repro.obs.registry import (Counter, Family, Gauge, Histogram,
                                MetricsRegistry)
from repro.obs.trace import (Span, Trace, Tracer, chrome_trace,
                             chrome_trace_json, validate_trace)


@dataclasses.dataclass
class Observability:
    """One server's observability bundle: metric sink + tracer +
    sampling policy (+ optionally the quality plane's auditor, which
    servers pick up and feed sampled requests). Build with
    :meth:`create`."""

    registry: MetricsRegistry
    tracer: Tracer | None = None
    stage_sample_every: int = 128
    auditor: ShadowAuditor | None = None

    @classmethod
    def create(cls, *, trace_capacity: int = 256,
               stage_sample_every: int = 128,
               tracing: bool = True) -> "Observability":
        return cls(registry=MetricsRegistry(),
                   tracer=Tracer(capacity=trace_capacity)
                   if tracing else None,
                   stage_sample_every=stage_sample_every)

    def sample_stages(self, launch_seq: int) -> bool:
        """Deterministic stage-detail sampling: every Nth launch."""
        return (self.stage_sample_every > 0
                and launch_seq % self.stage_sample_every == 0)


__all__ = [
    "Observability",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Family",
    "Tracer", "Trace", "Span", "chrome_trace", "chrome_trace_json",
    "validate_trace",
    "prometheus_text", "parse_prometheus_text", "write_jsonl_snapshot",
    "ObsHTTPServer", "start_exporter",
    "phase",
    "ShadowAuditor", "recall_at_k", "per_query_recall", "wilson_interval",
    "sample_stats", "FUNNEL_STAGES",
]
