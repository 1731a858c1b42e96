"""Deadline-based micro-batching front-end over the staged pipeline.

``AsyncSeismicServer`` accepts single queries (``submit``) from any
thread and coalesces whatever is in flight into fixed-shape
``[width, query_nnz]`` launches of the jitted ``search_pipeline``
(dispatch on batch-full OR oldest-deadline-expiry, never recompiling).
Launch widths come from a pre-compiled LADDER (default ``8/32/128``
clipped to ``max_batch``): each dispatch picks the smallest compiled
width covering the coalesced batch, so a lone tail request stops
paying the full ``max_batch`` of padded pipeline work. Every width is
compiled at warmup; per-width dispatch counts land in telemetry
(``launch_width_<w>``). The server then fulfills per-request futures. Around that core sit admission
control (bounded queue, ``reject`` / ``shed_oldest``), a quantized-
fingerprint LRU result cache, request coalescing (concurrently
in-flight requests with identical quantized fingerprints share one
launch slot — the LRU only catches repeats *after* the first
completes), and telemetry (per-stage latency when ``stage_timing`` is
on, queue depth, batch occupancy, cache hit-rate).

Observability (``obs=Observability.create()``): a trace id is minted
at ``submit`` and every request produces a span tree — ``request``
root, ``queue_wait`` and ``launch`` children, and (on every
``stage_sample_every``-th launch) the six ``stage_*`` children plus
per-``refine_round_<j>`` grandchildren, recorded into the tracer's
ring buffer and exportable as Chrome trace-event JSON. The registry
gains serving gauges (cache hit-rate, shed/reject rate, deadline-miss
rate, per-width occupancy, tuned-policy drift). See
``src/repro/obs/README.md`` for the span model and metric names.

Host phases (always on, ``repro.obs.phase``): the batcher thread runs
each launch as ``batch_wait`` (the whole ``next_batch`` call: the wait
on an empty queue and the deadline hold), ``pack`` (width, counters,
the fixed-shape arrays), ``dispatch`` (the ``search_pipeline`` call,
with the host-to-device put of the batch), ``device_wait`` (its
``block_until_ready``), ``to_host`` (the outputs to numpy) and
``fulfil`` (launch accounting and the futures). Each is a
``seismic.<phase>`` annotation in the profiler's host plane and a
``seismic_latency_seconds{span=<phase>}`` histogram, one record per
launch. The telemetry's ``compilations`` counter counts the process's
backend compilations once the server has warmed up (``start``).

The synchronous ``SeismicServer`` facade in ``engine`` remains the
simple offline-batch path; this class is the serving path every
future scaling layer (sharded serving, replication) plugs into.
"""
from __future__ import annotations

import dataclasses
import struct
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import SeismicIndex
from repro.graph.refine import validate_refine_params
from repro.obs.phase import phase
from repro.retrieval import SearchParams, search_pipeline
from repro.retrieval.pipeline import run_pipeline_staged, stage_fns
from repro.serve.cache import LRUCache, fingerprint_candidates
from repro.serve.queue import Request, RequestQueue, ServeFuture
from repro.serve.telemetry import ServerTelemetry
from repro.sparse.ops import PaddedSparse


@dataclasses.dataclass
class ServeResult:
    """Per-request retrieval result with serving metadata."""

    ids: np.ndarray            # int32 [k], -1 padding
    scores: np.ndarray         # f32 [k]
    docs_evaluated: int
    cached: bool = False
    coalesced: bool = False    # fulfilled from another request's slot
    latency_s: float = 0.0     # submit -> fulfil wall time
    occupancy: int = 0         # real queries in the serving launch


def attach_stage_spans(tracer, trace, parent, triples) -> None:
    """Turn ``run_pipeline_staged`` span triples ``(name, t0, t1)``
    into child spans of ``parent``: ``stage_<name>`` for the six
    stages, with ``refine_round_<j>`` entries nested under the
    ``stage_refine`` span."""
    rounds = [t for t in triples if t[0].startswith("refine_round_")]
    refine_span = None
    for name, a, b in triples:
        if name.startswith("refine_round_"):
            continue
        sp = tracer.add_span(trace, f"stage_{name}", a, b, parent=parent)
        if name == "refine":
            refine_span = sp
    for name, a, b in rounds:
        tracer.add_span(trace, name, a, b,
                        parent=refine_span if refine_span is not None
                        else parent)


class AsyncSeismicServer:
    """Micro-batching async retrieval server over one Seismic index.

    Parameters
    ----------
    max_batch     maximum launch width; a dispatch never carries more
                  than this many distinct requests.
    launch_widths ascending pre-compiled launch widths (the ladder).
                  ``None`` selects the default rungs ``(8, 32, 128)``
                  clipped to ``max_batch`` (which is always the top
                  rung). Each dispatch pads to the smallest rung
                  covering the batch instead of always ``max_batch``.
    query_nnz     fixed per-query nnz width; longer queries keep their
                  ``query_nnz`` heaviest coordinates.
    deadline_s    default max time a request may wait for co-batching
                  before a (possibly partial) launch is forced.
    queue_bound   admission limit; beyond it ``admission`` applies
                  ("reject" new requests or "shed_oldest" queued ones).
    cache_size    LRU entries keyed on quantized query fingerprints;
                  0 disables caching.
    coalesce      share one launch slot among concurrently in-flight
                  requests with identical quantized fingerprints (the
                  LRU cache only catches repeats after the first
                  completes; this catches the simultaneous burst).
    stage_timing  serve EVERY launch through the stage-by-stage
                  pipeline and record ``stage_*`` latency histograms
                  (slightly slower than the fused launch; with ``obs``
                  attached prefer its sampled stage tracing instead).
    obs           an ``repro.obs.Observability`` bundle: enables
                  request tracing, the serving gauges, and sampled
                  staged launches. When given and ``telemetry`` is
                  not, the telemetry facade writes into the bundle's
                  registry so one scrape sees everything.
    auditor       a ``repro.obs.ShadowAuditor`` (defaults to
                  ``obs.auditor``): every ``audit_sample_every``-th
                  served request is copied off the hot path for
                  shadow-oracle recall auditing; audited launches run
                  the staged pipeline with funnel captures and carry
                  an ``audit`` span. The auditor's worker lifecycle is
                  the owner's (start it or its queue sheds).
    deadline_grace_s  slack before a dispatch past its deadline counts
                  as a deadline MISS (deadline-triggered dispatches
                  legitimately run a hair past it; a miss means the
                  batcher fell behind by more than this).
    """

    DEFAULT_WIDTHS = (8, 32, 128)

    def __init__(self, index: SeismicIndex, params: SearchParams, *,
                 max_batch: int = 32, query_nnz: int = 32,
                 launch_widths: tuple[int, ...] | None = None,
                 deadline_s: float = 2e-3, queue_bound: int = 1024,
                 admission: str = "reject", cache_size: int = 0,
                 coalesce: bool = True, stage_timing: bool = False,
                 telemetry: ServerTelemetry | None = None,
                 obs=None, auditor=None,
                 deadline_grace_s: float = 1e-3):
        validate_refine_params(index, params)   # fail before threads spin
        from repro.tune.policy import validate_tuned_index
        validate_tuned_index(index)             # stale TunedPolicy -> now
        self.index = index
        self.params = params
        self.max_batch = max_batch
        if launch_widths is None:
            launch_widths = tuple(w for w in self.DEFAULT_WIDTHS
                                  if w < max_batch)
        else:
            if any(w <= 0 or w > max_batch for w in launch_widths):
                raise ValueError(
                    f"launch_widths {launch_widths} must lie in "
                    f"[1, max_batch={max_batch}]")
            launch_widths = tuple(w for w in launch_widths
                                  if w < max_batch)
        # max_batch is always the top rung, so every batch has a cover
        self.launch_widths = tuple(sorted(set(launch_widths))) \
            + (max_batch,)
        self.query_nnz = query_nnz
        self.deadline_s = deadline_s
        self.deadline_grace_s = deadline_grace_s
        self.stage_timing = stage_timing
        self.obs = obs
        self.queue = RequestQueue(bound=queue_bound, policy=admission)
        self.cache = LRUCache(cache_size) if cache_size > 0 else None
        self.coalesce = coalesce
        self._inflight: dict[bytes, Request] = {}
        self._coalesce_lock = threading.Lock()
        # serving epoch: bumped on every swap_index. Baked into every
        # cache/coalesce key, so results computed against an earlier
        # index can never be served after a swap (their keys become
        # unreachable — no stale top-k survives a mutation).
        self.epoch = 0
        self._swap_lock = threading.RLock()
        if telemetry is not None:
            self.telemetry = telemetry
        else:
            self.telemetry = ServerTelemetry(
                registry=obs.registry if obs is not None else None)
        self._tracer = obs.tracer if obs is not None else None
        self.auditor = auditor if auditor is not None \
            else getattr(obs, "auditor", None)
        # an auditor needs the staged programs compiled: audited
        # launches run staged to capture the funnel's memberships
        staged_wanted = stage_timing or self.auditor is not None or (
            obs is not None and obs.stage_sample_every > 0)
        self._fns = stage_fns(index, params) if staged_wanted else None
        # launch counters are shared by every thread that may dispatch
        # (one worker here; N replica workers in ReplicaSeismicServer)
        self._stats_lock = threading.Lock()
        self._launch_seq = 0
        self._width_stats: dict[int, list[int]] = {}   # w -> [launches,
        self._ev_sum = 0.0                             #       slots]
        self._ev_n = 0
        self._register_gauges()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------ observability

    def _event(self, name: str):
        """Current value of one ``seismic_events_total`` counter."""
        return self.telemetry.registry.counter(
            "seismic_events_total", labels=("event",)).labels(name).value

    def _register_gauges(self) -> None:
        """Derived serving gauges, evaluated lazily at scrape time.
        One bundle per server: sharing an Observability registry across
        servers would make the last one win these callbacks."""
        reg = self.telemetry.registry
        reg.gauge("seismic_index_epoch",
                  "Generation of the index being served (bumped on "
                  "every swap_index / mutation publish)").labels() \
            .set_fn(lambda: self.epoch)
        reg.gauge("seismic_cache_hit_rate",
                  "LRU result-cache hit rate since start").labels() \
            .set_fn(lambda: self.cache.stats()["hit_rate"]
                    if self.cache is not None else 0.0)
        reg.gauge("seismic_shed_rate",
                  "(shed + rejected) / submitted requests").labels() \
            .set_fn(lambda: (self._event("shed")
                             + self._event("rejected"))
                    / max(1, self._event("requests")))
        reg.gauge("seismic_deadline_miss_rate",
                  "dispatches later than deadline + grace / dispatched"
                  ).labels() \
            .set_fn(lambda: self._event("deadline_missed")
                    / max(1, self._event("dispatched")))
        self._width_occ = reg.gauge(
            "seismic_launch_width_occupancy",
            "Mean real-request fill fraction per compiled launch width",
            ("width",))
        self._ev_mean = reg.gauge(
            "seismic_docs_evaluated_mean",
            "Running mean docs exactly scored per served query"
            ).labels()
        from repro.tune.policy import KNOB_FIELDS
        self._tuned_match = next(
            (t for t in (getattr(self.index, "tuned", ()) or ())
             if all(getattr(t, f) == getattr(self.params, f)
                    for f in KNOB_FIELDS)), None)
        if self._tuned_match is not None:
            cost = self._tuned_match.measured_cost
            reg.gauge("seismic_tuned_drift_docs",
                      "Served mean docs_evaluated minus the attached "
                      "TunedPolicy's measured cost", ("target",)) \
                .labels(f"{self._tuned_match.target:g}") \
                .set_fn(lambda: (self._ev_sum / self._ev_n - cost)
                        if self._ev_n else 0.0)
            reg.gauge("seismic_tuned_drift_ratio",
                      "Served mean docs_evaluated over the attached "
                      "TunedPolicy's measured cost", ("target",)) \
                .labels(f"{self._tuned_match.target:g}") \
                .set_fn(lambda: (self._ev_sum / self._ev_n / cost)
                        if self._ev_n and cost else 1.0)

    # ------------------------------------------------------- lifecycle

    def start(self, warmup: bool = True) -> "AsyncSeismicServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        if self.queue.closed:
            raise RuntimeError("server was stopped; its queue is closed "
                               "— build a new AsyncSeismicServer")
        if warmup:
            self.warmup()
        self.telemetry.count_compilations()
        self._thread = threading.Thread(target=self._worker,
                                        name="seismic-batcher",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Close admission, drain queued requests, join the worker."""
        self.queue.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "AsyncSeismicServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def warmup(self) -> None:
        """Compile every ladder width before serving traffic — the
        fused program always, plus the staged (and per-refine-round)
        programs when stage timing or sampled stage tracing is on."""
        self._warmup_for(self.index, self.params, self._fns)

    def _warmup_for(self, index, params, fns) -> None:
        """Warmup body against an explicit (index, params, fns) triple
        so ``swap_index`` can compile the incoming index BEFORE it is
        published (first post-swap dispatch must not stall every
        in-flight deadline behind compilation)."""
        for width in self.launch_widths:
            coords = jnp.zeros((width, self.query_nnz), jnp.int32)
            vals = jnp.zeros((width, self.query_nnz), jnp.float32)
            if not self.stage_timing:
                jax.block_until_ready(search_pipeline(
                    index, PaddedSparse(coords, vals, index.dim),
                    params))
            if fns is not None:
                jax.block_until_ready(run_pipeline_staged(
                    index, coords, vals, params,
                    fns=fns, split_refine=True))

    # ----------------------------------------------------- index swap

    def swap_index(self, index: SeismicIndex,
                   params: SearchParams | None = None, *,
                   warmup: bool = True) -> int:
        """Atomically publish a new index (and optionally new params);
        returns the new serving epoch.

        Safe against in-flight launches: the (index, fns, params)
        triple is snapshotted under ``_swap_lock`` by every dispatch,
        so a launch runs entirely against one generation — never a torn
        mix. The epoch bump makes every pre-swap cache/coalesce key
        unreachable, so results computed against the old index are
        never served again (see the stale-cache regression test).
        Requests already dispatched against the old index still
        complete and are fulfilled — their results are cached under
        old-epoch keys, i.e. dropped.

        With ``warmup`` (default) the new index is compiled at every
        ladder width before publication, off the serving path.
        """
        params = self.params if params is None else params
        validate_refine_params(index, params)
        from repro.tune.policy import validate_tuned_index
        validate_tuned_index(index)
        fns = stage_fns(index, params) if self._fns is not None else None
        if warmup:
            self._warmup_for(index, params, fns)
        with self._swap_lock:
            self._publish_swap(index, params, fns)
            epoch = self.epoch
        # re-derive gauges bound to the served pair (tuned-policy drift
        # targets, cache hit-rate closures): families are idempotent and
        # set_fn callbacks overwrite, so re-registration rebinds them
        self._register_gauges()
        self.telemetry.inc("swaps")
        return epoch

    def _publish_swap(self, index, params, fns) -> None:
        """Swap commit point; runs under ``_swap_lock``. Subclasses
        extend it to keep their mirrors in step (replica server)."""
        self.index = index
        self.params = params
        self._fns = fns
        self.epoch += 1

    def apply_mutation(self, mutable, mutate_fn=None, *,
                       warmup: bool = True) -> int:
        """Serve a :class:`repro.core.mutate.MutableSeismicIndex`'s
        current snapshot: optionally run ``mutate_fn(mutable)`` first
        (inserts / deletes / compaction), then publish the mutated
        snapshot via :meth:`swap_index`. Returns the new serving epoch.
        """
        if mutate_fn is not None:
            mutate_fn(mutable)
        return self.swap_index(mutable.index, warmup=warmup)

    # ------------------------------------------------------ submission

    def submit(self, coords, vals,
               deadline_s: float | None = None) -> ServeFuture:
        """Enqueue one sparse query; returns its completion future.

        Cache hits fulfil immediately without touching the queue; a
        request whose fingerprint matches one already in flight
        attaches to that request's launch slot instead of occupying
        its own (``coalesce``). Rejected / shed requests get a failed
        future (``status`` set), never an exception on the submitting
        thread. With tracing on, every path ends the request's trace
        with a ``status`` attr.
        """
        tel = self.telemetry
        tel.inc("requests")
        c, v = self._normalize(coords, vals)
        now = time.monotonic()
        tr = self._tracer.start_trace("request", now) \
            if self._tracer is not None else None
        key = None
        cand_keys: list[bytes] = []
        if self.cache is not None or self.coalesce:
            # the serving epoch prefixes every cache/coalesce key: a
            # swap_index bumps it, instantly orphaning all results
            # computed against the previous index (stale-cache fix).
            # Multiple fingerprint candidates cover scale-bucket
            # boundary jitter (see serve.cache): probe all, file under
            # the primary.
            ep = struct.pack("<Q", self.epoch)
            cand_keys = [ep + fp for fp in fingerprint_candidates(c, v)]
            key = cand_keys[0]
        if self.cache is not None:
            hit = self.cache.get_any(cand_keys)   # counted by the LRU
            if hit is not None:
                fut = ServeFuture()
                ids, scores, ev = hit
                fut._set(ServeResult(ids=ids.copy(), scores=scores.copy(),
                                     docs_evaluated=ev, cached=True))
                if tr is not None:
                    self._tracer.end_trace(tr, time.monotonic(),
                                           status="done", cached=True)
                return fut
        req = Request(coords=c, vals=v, submit_t=now,
                      deadline=now + (self.deadline_s if deadline_s is None
                                      else deadline_s),
                      future=ServeFuture(), cache_key=key, trace=tr)
        # the check-attach-or-enqueue-and-register must be atomic, or
        # two racing duplicates both become primaries / a follower
        # attaches to a request whose slot already fulfilled
        with self._coalesce_lock:
            if self.coalesce:
                primary = next(
                    (p for ck in cand_keys
                     if (p := self._inflight.get(ck)) is not None), None)
                if primary is not None:
                    primary.followers.append((req.future, now, tr))
                    if tr is not None:
                        tr.root.attrs["coalesced_into"] = \
                            primary.trace.trace_id \
                            if primary.trace is not None else "untraced"
                    tel.inc("coalesced")
                    return req.future
            status, shed = self.queue.put(req)
            if status == "ok" and self.coalesce:
                self._inflight[key] = req
            if shed is not None:
                self._unregister(shed)
        if status != "ok":
            tel.inc(status)                 # "rejected" or "closed"
            req.future._fail(status)
            if tr is not None:
                self._tracer.end_trace(tr, time.monotonic(),
                                       status=status)
        elif shed is not None:
            tel.inc("shed")
            self._fail_all(shed, "shed")
        tel.observe_queue_depth(self.queue.depth)
        return req.future

    def search(self, queries: PaddedSparse,
               deadline_s: float | None = None):
        """Synchronous batch convenience: submit every row, wait all.

        Returns an ``engine.RetrievalResult`` so callers can swap the
        sync facade for the async server without changing result
        handling. Rejected/shed rows come back as -1 ids.
        """
        from repro.serve.engine import RetrievalResult
        coords = np.asarray(queries.coords)
        vals = np.asarray(queries.vals)
        futs = [self.submit(coords[i], vals[i], deadline_s)
                for i in range(coords.shape[0])]
        ids = np.full((len(futs), self.params.k), -1, np.int32)
        scores = np.full((len(futs), self.params.k), -np.inf, np.float32)
        ev = np.zeros((len(futs),), np.int32)
        for i, f in enumerate(futs):
            f.wait()
            if f.status == "done":
                r = f._result
                ids[i], scores[i], ev[i] = r.ids, r.scores, \
                    r.docs_evaluated
        return RetrievalResult(ids=ids, scores=scores, docs_evaluated=ev)

    # ---------------------------------------------------------- worker

    def _next_batch(self) -> list[Request] | None:
        """``queue.next_batch`` as the ``batch_wait`` phase; the
        shutdown call that returns ``None`` is not recorded."""
        with phase(self.telemetry, "batch_wait") as wait:
            batch = self.queue.next_batch(self.max_batch)
            wait.keep = batch is not None
        return batch

    def _worker(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            try:
                self._launch(batch)
            except Exception as e:   # noqa: BLE001 — fail the batch, keep serving
                for r in batch:
                    self._fail_all(r, f"error: {type(e).__name__}: {e}")

    # --------------------------------------------- in-flight coalescing

    def _unregister(self, req: Request) -> None:
        """Drop ``req`` from the in-flight map (caller holds the lock
        or owns the request). No more followers can attach after this."""
        if req.cache_key is not None \
                and self._inflight.get(req.cache_key) is req:
            del self._inflight[req.cache_key]

    def _finish_inflight(self, req: Request) -> list:
        """Atomically retire ``req`` from the in-flight map and snapshot
        its followers; later duplicates become fresh primaries."""
        with self._coalesce_lock:
            self._unregister(req)
            return req.followers

    def _fail_all(self, req: Request, status: str) -> None:
        """Fail a request's future and every coalesced follower.

        Completion is first-writer-wins (``ServeFuture._fail`` returns
        whether this call transitioned), so a batch-wide failure after
        a partial fulfil leaves already-``done`` futures — and their
        already-ended traces — untouched."""
        now = time.monotonic()
        for f, _, ftr in self._finish_inflight(req):
            if f._fail(status) and ftr is not None:
                self._tracer.end_trace(ftr, now, status=status)
        if req.future._fail(status) and req.trace is not None:
            self._tracer.end_trace(req.trace, now, status=status)

    def _pick_width(self, n: int) -> int:
        """Smallest pre-compiled ladder rung covering ``n`` requests."""
        for w in self.launch_widths:
            if w >= n:
                return w
        return self.max_batch

    def _next_seq(self) -> int:
        with self._stats_lock:
            seq = self._launch_seq
            self._launch_seq += 1
            return seq

    def _pack(self, batch: list[Request],
              width: int) -> tuple[np.ndarray, np.ndarray]:
        """Batch rows -> fixed-shape [width, query_nnz] launch arrays."""
        coords = np.zeros((width, self.query_nnz), np.int32)
        vals = np.zeros((width, self.query_nnz), np.float32)
        for i, r in enumerate(batch):
            coords[i], vals[i] = r.coords, r.vals
        return coords, vals

    def _execute(self, index, fns, coords: np.ndarray, vals: np.ndarray,
                 staged: bool, delay_s: float = 0.0, *,
                 audit: bool = False, params: SearchParams | None = None):
        """One pipeline execution against ``index``; returns host arrays
        plus wall-time bounds and (staged only) per-stage span triples.

        Runs as the ``dispatch``, ``device_wait`` and ``to_host``
        phases; the staged path blocks stage by stage inside
        ``dispatch``. ``delay_s`` injects artificial per-launch latency
        INSIDE the timed window (replica benchmarks / balancer tests:
        the EWMA must see it). ``audit`` (staged only) additionally
        probes the funnel's membership captures for the shadow
        auditor."""
        tel = self.telemetry
        p = self.params if params is None else params
        triples: list[tuple[str, float, float]] = []
        probed: dict[str, object] = {}
        t0 = time.monotonic()
        with phase(tel, "dispatch"):
            if delay_s > 0.0:
                time.sleep(delay_s)
            if staged:
                out = run_pipeline_staged(
                    index, jnp.asarray(coords), jnp.asarray(vals),
                    p, fns=fns,
                    record=lambda s, dt: tel.record_latency(f"stage_{s}",
                                                            dt),
                    span_cb=lambda name, a, b: triples.append((name, a, b)),
                    split_refine=True, probe=probed.__setitem__,
                    audit=audit)
            else:
                out = search_pipeline(
                    index,
                    PaddedSparse(jnp.asarray(coords), jnp.asarray(vals),
                                 index.dim),
                    p)
        with phase(tel, "device_wait"):
            scores, ids, ev = jax.block_until_ready(out)
        t1 = time.monotonic()
        with phase(tel, "to_host"):
            ids, scores, ev = np.asarray(ids), np.asarray(scores), \
                np.asarray(ev)
        return ids, scores, ev, t0, t1, triples, probed

    def _account(self, n: int, width: int, ev: np.ndarray) -> None:
        """Post-execution telemetry shared by every dispatch path."""
        tel = self.telemetry
        tel.inc("batches")
        tel.observe_occupancy(n)
        with self._stats_lock:
            ws = self._width_stats.setdefault(width, [0, 0])
            ws[0] += 1
            ws[1] += n
            occ = ws[1] / (ws[0] * width)
            self._ev_sum += float(ev[:n].sum())
            self._ev_n += n
            ev_mean = self._ev_sum / self._ev_n
        self._width_occ.labels(str(width)).set(occ)
        self._ev_mean.set(ev_mean)

    def _launch(self, batch: list[Request], *, index=None, fns=None,
                delay_s: float = 0.0, span_attrs: dict | None = None,
                on_timing=None) -> None:
        """One fixed-shape pipeline launch serving ``len(batch)`` rows.

        The keyword hooks are the replica-server seam: ``index``/``fns``
        select a replica's copy (default: the server's own), ``delay_s``
        injects artificial latency, ``span_attrs`` lands extra attrs on
        every launch span (e.g. ``replica=rid``), and ``on_timing(
        launch_seconds, stage_seconds)`` feeds the balancer's EWMA."""
        tel = self.telemetry
        n = len(batch)
        with phase(tel, "pack"):
            width = self._pick_width(n)
            tel.inc(f"launch_width_{width}")
            tel.inc("dispatched", n)
            seq = self._next_seq()
            audit_rows = self.auditor.plan(n) \
                if self.auditor is not None else ()
            # one atomic snapshot of the serving generation: a
            # concurrent swap_index can never tear an old index against
            # new stage fns or params inside a single launch
            with self._swap_lock:
                if index is None:
                    index = self.index
                if fns is None:
                    fns = self._fns
                params = self.params
            have_fns = fns is not None
            capture = bool(audit_rows) and have_fns
            staged = self.stage_timing or capture or (
                have_fns
                and self.obs is not None and self.obs.sample_stages(seq))
            coords, vals = self._pack(batch, width)
        dispatch_t = time.monotonic()
        ids, scores, ev, t0, t1, triples, probed = self._execute(
            index, fns, coords, vals, staged, delay_s, audit=capture,
            params=params)
        tel.record_latency("launch", t1 - t0)
        if on_timing is not None:
            on_timing(t1 - t0,
                      {name: b - a for name, a, b in triples})
        audit_span = None
        if audit_rows:
            a0 = time.monotonic()
            for i in audit_rows:
                self.auditor.feed(coords[i], vals[i], ids[i],
                                  captures=probed if capture else None,
                                  row=i)
            audit_span = (a0, time.monotonic())
        with phase(tel, "fulfil"):
            self._account(n, width, ev)
            self._fulfil(batch, ids, scores, ev, dispatch_t=dispatch_t,
                         t1=t1, width=width, seq=seq, staged=staged,
                         triples=triples, span_attrs=span_attrs,
                         audit_span=audit_span)

    def _fulfil(self, batch: list[Request], ids: np.ndarray,
                scores: np.ndarray, ev: np.ndarray, *, dispatch_t: float,
                t1: float, width: int, seq: int, staged: bool,
                triples=(), span_attrs: dict | None = None,
                audit_span: tuple[float, float] | None = None) -> None:
        """Fulfil every request (and coalesced follower) of a batch from
        the launch's result rows; closes caches, histograms, spans."""
        tel = self.telemetry
        n = len(batch)
        attrs = span_attrs or {}
        done_t = time.monotonic()
        leader = batch[0]
        served = 0
        for i, r in enumerate(batch):
            if self.cache is not None and r.cache_key is not None:
                # copies: don't let caller mutation poison hits, don't
                # pin the whole launch arrays via views
                self.cache.put(r.cache_key,
                               (ids[i].copy(), scores[i].copy(),
                                int(ev[i])))
            if dispatch_t > r.deadline + self.deadline_grace_s:
                tel.inc("deadline_missed")
            tel.record_latency("queue_wait", dispatch_t - r.submit_t)
            tel.record_latency("request_e2e", done_t - r.submit_t)
            if r.trace is not None:
                self._tracer.add_span(r.trace, "queue_wait",
                                      r.submit_t, dispatch_t)
                launch_span = self._tracer.add_span(
                    r.trace, "launch", dispatch_t, t1, width=width,
                    occupancy=n, batch_seq=seq, staged=staged, **attrs)
                # stages ran once for the batch: their spans attach to
                # the batch leader's launch span only
                if r is leader and staged:
                    attach_stage_spans(self._tracer, r.trace,
                                       launch_span, triples)
                # likewise the audit feed (one per launch): root-level
                # on the leader, it runs after the launch window
                if r is leader and audit_span is not None:
                    self._tracer.add_span(r.trace, "audit",
                                          audit_span[0], audit_span[1])
            # retire from the in-flight map BEFORE fulfilling: once the
            # followers snapshot is taken no new duplicate can attach
            # to this slot (they re-enter as cache hits / new primaries)
            followers = self._finish_inflight(r)
            for f, t_sub, ftr in followers:
                # a follower attached mid-execution waited 0 in queue
                tel.record_latency("queue_wait",
                                   max(0.0, dispatch_t - t_sub))
                tel.record_latency("request_e2e",
                                   max(0.0, done_t - t_sub))
                if f._set(ServeResult(
                        ids=ids[i].copy(), scores=scores[i].copy(),
                        docs_evaluated=int(ev[i]), coalesced=True,
                        latency_s=max(0.0, done_t - t_sub),
                        occupancy=n)) and ftr is not None:
                    # a follower that attached mid-execution has
                    # t_sub > dispatch_t: clamp its spans into
                    # [t_sub, ...] so the tree stays valid (the
                    # histogram above clamps; spans must too)
                    f_disp = max(t_sub, dispatch_t)
                    f_end = max(f_disp, t1)
                    self._tracer.add_span(ftr, "queue_wait",
                                          t_sub, f_disp)
                    self._tracer.add_span(ftr, "launch", f_disp, f_end,
                                          width=width, occupancy=n,
                                          batch_seq=seq, staged=staged,
                                          **attrs)
                    self._tracer.end_trace(ftr, max(done_t, f_end),
                                           status="done")
            if r.future._set(ServeResult(
                    ids=ids[i], scores=scores[i],
                    docs_evaluated=int(ev[i]), cached=False,
                    latency_s=done_t - r.submit_t, occupancy=n)) \
                    and r.trace is not None:
                self._tracer.end_trace(r.trace, done_t, status="done",
                                       docs_evaluated=int(ev[i]))
            served += 1 + len(followers)
        tel.inc("served", served)

    # --------------------------------------------------------- helpers

    def _normalize(self, coords, vals) -> tuple[np.ndarray, np.ndarray]:
        """Pad/truncate one sparse query to the fixed ``query_nnz``."""
        c = np.asarray(coords, np.int32).ravel()
        v = np.asarray(vals, np.float32).ravel()
        if c.shape != v.shape:
            raise ValueError(f"coords {c.shape} vs vals {v.shape}")
        if c.size > self.query_nnz:          # keep heaviest coordinates
            keep = np.argpartition(v, -self.query_nnz)[-self.query_nnz:]
            c, v = c[keep], v[keep]
        out_c = np.zeros((self.query_nnz,), np.int32)
        out_v = np.zeros((self.query_nnz,), np.float32)
        out_c[:c.size], out_v[:v.size] = c, v
        out_c[out_v <= 0] = 0                # canonical padding slots
        out_v[out_v <= 0] = 0.0
        return out_c, out_v

    def telemetry_export(self) -> dict:
        """Telemetry snapshot plus cache stats, as one plain dict."""
        out = self.telemetry.export()
        out["cache"] = self.cache.stats() if self.cache is not None \
            else None
        return out
