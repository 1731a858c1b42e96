"""Serving subsystem: async deadline-based micro-batching over the
staged retrieval pipeline (see README.md in this package).

    engine      thin synchronous facades (SeismicServer, LMDecoder)
    queue       bounded deadline request queue + admission control
    batcher     AsyncSeismicServer (the micro-batching server)
    replica     ReplicaSeismicServer (N replica workers — mirrored or
                doc-sharded — behind the one queue)
    balancer    StageTimingBalancer (EWMA-cost virtual-time dispatch)
    cache       quantized-fingerprint LRU result cache
    telemetry   compatibility facade over repro.obs.MetricsRegistry
                (plain-dict export shape unchanged)

Pass ``obs=repro.obs.Observability.create()`` to either server for
request tracing, the serving gauges and sampled per-stage spans —
one registry scraped by the ``repro.obs`` exporters. See ``src/repro/obs/README.md``.
"""
from repro.serve.balancer import StageTimingBalancer
from repro.serve.batcher import AsyncSeismicServer, ServeResult
from repro.serve.cache import LRUCache, query_fingerprint
from repro.serve.engine import LMDecoder, RetrievalResult, SeismicServer
from repro.serve.queue import (ADMISSION_POLICIES, Request, RequestQueue,
                               ServeFuture)
from repro.serve.replica import ReplicaSeismicServer
from repro.serve.telemetry import Histogram, ServerTelemetry

__all__ = [
    "AsyncSeismicServer", "ServeResult",
    "ReplicaSeismicServer", "StageTimingBalancer",
    "SeismicServer", "RetrievalResult", "LMDecoder",
    "RequestQueue", "Request", "ServeFuture", "ADMISSION_POLICIES",
    "LRUCache", "query_fingerprint",
    "Histogram", "ServerTelemetry",
]
