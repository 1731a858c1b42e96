"""Host phases of a serving thread, on the profiler's clock.

``with phase(telemetry, "pack"):`` opens a
``jax.profiler.TraceAnnotation`` named ``seismic.pack`` and, on exit,
records the block's wall time into the server telemetry's
``seismic_latency_seconds{span="pack"}`` histogram. The annotation
lands in the profiler's host plane, on the clock the device trace is
aligned to, so a device idle gap can be named by the phase the host
thread was in; the histogram carries the same phase to
``telemetry_export()``, the Prometheus exporter and the benchmark. With
no profiler session the annotation costs about a microsecond.
"""
from __future__ import annotations

import time

import jax
import jax.profiler

PREFIX = "seismic."


class phase:
    """Context manager timing one host phase (see module doc).

    ``telemetry`` may be ``None``: the annotation still opens, nothing
    is recorded. Setting ``keep = False`` inside the block drops its
    record (a blocking call that ended without doing the phase's work).
    """

    __slots__ = ("_tel", "name", "keep", "_ann", "_t0")

    def __init__(self, telemetry, name: str):
        self._tel = telemetry
        self.name = name
        self.keep = True

    def __enter__(self) -> "phase":
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.keep and self._tel is not None:
            self._tel.record_latency(self.name, dt)


__all__ = ["phase", "PREFIX"]
