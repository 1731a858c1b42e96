"""Serving telemetry — now a thin compatibility facade over the
unified metrics registry (:mod:`repro.obs.registry`).

``ServerTelemetry`` keeps its PR-2 API (``record_latency`` / ``inc`` /
``observe_occupancy`` / ``observe_queue_depth`` / ``export``) and its
plain-dict export shape, but every record lands in a
``MetricsRegistry`` as a labeled metric family:

    record_latency(name, s)   -> seismic_latency_seconds{span=name}
    inc(name, n)              -> seismic_events_total{event=name}
    observe_occupancy(n)      -> seismic_launch_occupancy_total{n_real=n}
    observe_queue_depth(d)    -> seismic_queue_depth / _queue_depth_max
    count_compilations()      -> seismic_events_total{event=
                                 "compilations"} from then on

so the same numbers the load benchmarks always consumed as dicts are
now ALSO scrapeable through the Prometheus / JSONL exporters, with no
double bookkeeping. Pass a shared registry (e.g. from an
``Observability`` bundle) to merge server telemetry with the tracing
and quality metrics; by default each facade owns a fresh one.

``Histogram`` re-exports the registry histogram: log-spaced buckets
with quantile estimates that are monotone in ``p`` and always inside
``[vmin, vmax]`` (a single cumulative-count walk shared with every
registry histogram — the PR-2 first-bucket geometric-mean estimate and
its odd ``vmin``/``vmax`` clamping are gone).
"""
from __future__ import annotations

import threading
import weakref

import jax.monitoring

from repro.obs.registry import Histogram, MetricsRegistry

__all__ = ["Histogram", "ServerTelemetry"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# telemetries counting compilations; one listener, registered on first
# use, feeds them all (tests build many servers in one process)
_compile_sinks: "weakref.WeakSet[ServerTelemetry]" = weakref.WeakSet()
_compile_lock = threading.Lock()
_listening = False


def _on_jax_event(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    with _compile_lock:
        sinks = list(_compile_sinks)
    for tel in sinks:
        tel.inc("compilations")


class ServerTelemetry:
    """Thread-safe metric sink shared by the queue, batcher, and cache
    (compatibility facade over :class:`repro.obs.MetricsRegistry`).

    Latency histograms are keyed by name (``request_e2e``,
    ``queue_wait``, ``launch``, the batcher's host phases
    ``batch_wait`` / ``pack`` / ``dispatch`` / ``device_wait`` /
    ``to_host`` / ``fulfil``, and ``stage_<name>`` when the server
    runs the staged timing path); counters count requests / batches /
    admission events, and ``compilations`` once
    :meth:`count_compilations` was called; occupancy is a per-launch
    integer histogram.
    """

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._lat = self.registry.histogram(
            "seismic_latency_seconds",
            "Serving latency by span (request_e2e / queue_wait / "
            "launch / host phases / stage_*)", ("span",))
        self._events = self.registry.counter(
            "seismic_events_total",
            "Serving events (requests / batches / served / rejected / "
            "shed / coalesced / launch_width_* / ...)", ("event",))
        self._occ = self.registry.counter(
            "seismic_launch_occupancy_total",
            "Launches by real (un-padded) request count", ("n_real",))
        self._depth = self.registry.gauge(
            "seismic_queue_depth", "Admission queue depth at last "
            "observation").labels()
        self._depth_max = self.registry.gauge(
            "seismic_queue_depth_max", "Max observed admission queue "
            "depth").labels()

    def count_compilations(self) -> None:
        """From now on, count the process's backend compilations (every
        thread's, not only this server's) as the ``compilations``
        event; a server calls it once warmed up, so a non-zero count
        means a shape compiled while it served."""
        global _listening
        self._events.labels("compilations")    # reads 0 until one
        with _compile_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_jax_event)
                _listening = True
            _compile_sinks.add(self)

    def record_latency(self, name: str, seconds: float) -> None:
        self._lat.labels(name).record(seconds)

    def inc(self, name: str, n: int = 1) -> None:
        self._events.labels(name).inc(n)

    def observe_occupancy(self, n_real: int) -> None:
        self._occ.labels(str(n_real)).inc()

    def observe_queue_depth(self, depth: int) -> None:
        self._depth.set(depth)
        self._depth_max.set(max(self._depth_max.value, depth))

    def export(self) -> dict:
        """Plain-dict snapshot (JSON-serializable, no live references).

        Shape unchanged since PR 2 — benchmarks, tests, and the
        examples keep consuming it; the registry is the superset
        surface for exporters.
        """
        counters = {}
        for (event,), child in self._events.samples():
            counters[event] = child.value
        hists = {}
        for (span,), child in self._lat.samples():
            hists[span] = child.summary()
        occupancy = {}
        for (n_real,), child in self._occ.samples():
            occupancy[int(n_real)] = child.value
        launches = sum(occupancy.values())
        served = sum(k * v for k, v in occupancy.items())
        return {
            "counters": counters,
            "latency_s": {k: hists[k] for k in sorted(hists)},
            "batch": {
                "launches": launches,
                "mean_occupancy":
                    served / launches if launches else 0.0,
                "occupancy_counts": {str(k): v for k, v in
                                     sorted(occupancy.items())},
            },
            "queue": {"depth_max": self._depth_max.value,
                      "depth_last": self._depth.value},
        }
