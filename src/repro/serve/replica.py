"""Replica-parallel serving: N workers behind ONE admission queue.

``ReplicaSeismicServer`` composes the two halves that existed but had
never met: the async micro-batcher (``serve.batcher``) and the
doc-sharded index (``core.distributed``). One ``RequestQueue`` keeps
admission control, deadline batching, coalescing, and the LRU cache
exactly as in ``AsyncSeismicServer`` (this class subclasses it and
reuses its ``_launch`` internals); behind the queue a dispatcher thread
routes each micro-batch to one of N replica worker threads.

Two topologies:

  ``mirror``   every replica owns the SAME full index (one jit cache,
               zero extra memory for host threads; with per-device
               placement each replica would own a device copy). The
               dispatcher routes each batch to exactly one replica
               chosen by a :class:`repro.serve.balancer
               .StageTimingBalancer`: per-replica EWMA cost from the
               launch wall time (and the per-stage timings
               ``run_pipeline_staged`` exposes on staged launches)
               drives virtual-time dispatch — a slow replica gets
               proportionally fewer batches but is never starved.
               Results are bit-identical to ``AsyncSeismicServer`` at
               every replica count: same pipeline, same index, same
               launch-width ladder.

  ``shard``    replica r owns doc shard r of a ``build_sharded_index``
               stacked pytree, on the device that holds it (shard s
               on device s when there are as many devices as shards).
               Every batch fans out to ALL replicas; each scores its
               shard locally through ``core.distributed.search_shard``,
               which globalizes + masks pad hits via
               ``mask_shard_topk`` (the same invariant the
               ``shard_map`` path applies before its all-gather), and
               the last-finishing replica merges the
               per-shard top-k with the existing ``merge_topk`` and
               fulfils the batch. ``docs_evaluated`` is the sum over
               shards. This is the thread-parallel twin of
               ``make_distributed_search`` — the topology every later
               multi-host (``jax.process_index()``-style) deployment
               plugs into.

Telemetry: all ``AsyncSeismicServer`` metrics, plus per-replica
rollups in the same registry —

  ``seismic_replica_dispatches_total{replica}``  batches dispatched
  ``seismic_replica_cost_ewma_seconds{replica}`` balancer cost estimate
  ``seismic_replica_dispatch_share{replica}``    fraction of dispatches
  ``seismic_replica_inflight{replica}``          un-acked dispatches
  ``seismic_replica_stage_seconds{replica,stage}`` per-stage cost EWMA
                                                 (staged launches only)

and a ``replica`` attr on every launch span (``shard-merge`` on merged
shard launches).

``replica_delay_s`` injects artificial per-launch latency per replica
(inside the timed window, so the balancer's EWMA sees it) — the
deterministic knob the scaling/degradation benchmarks and the balancer
tests are built on; ``time.sleep`` releases the GIL, so delayed
replicas genuinely overlap.
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.distributed import search_shard, shard_views
from repro.retrieval import SearchParams
from repro.retrieval.merge import merge_topk
from repro.serve.balancer import StageTimingBalancer
from repro.serve.batcher import AsyncSeismicServer
from repro.serve.queue import Request

MODES = ("mirror", "shard")


class _ShardJob:
    """One micro-batch fanned out to every shard; the last replica to
    deposit its part runs the merge + fulfil."""

    __slots__ = ("batch", "coords", "vals", "width", "seq", "dispatch_t",
                 "parts", "t0_min", "t1_max", "failed", "_lock",
                 "_remaining", "view")

    def __init__(self, batch: list[Request], coords: np.ndarray,
                 vals: np.ndarray, width: int, seq: int,
                 dispatch_t: float, n_replicas: int, view: tuple):
        self.batch = batch
        self.coords = coords
        self.vals = vals
        self.width = width
        self.seq = seq
        self.dispatch_t = dispatch_t
        # (replicas, per_shard, n_docs, merge) snapshotted at dispatch:
        # every shard part of ONE job scores the SAME index generation
        # even if swap_index lands mid-fan-out (a torn job would merge
        # top-k lists from two different corpora)
        self.view = view
        self.parts: dict[int, tuple] = {}
        self.t0_min = float("inf")
        self.t1_max = 0.0
        self.failed = False
        self._lock = threading.Lock()
        self._remaining = n_replicas

    def add(self, rid: int, part, t0: float, t1: float) -> bool:
        """Deposit shard ``rid``'s result; True when this was the last
        outstanding part AND no part failed (caller merges)."""
        with self._lock:
            self.parts[rid] = part
            self.t0_min = min(self.t0_min, t0)
            self.t1_max = max(self.t1_max, t1)
            self._remaining -= 1
            return self._remaining == 0 and not self.failed

    def fail(self) -> bool:
        """Mark the job failed; True for the first failing shard only
        (that one fails the batch futures)."""
        with self._lock:
            self._remaining -= 1
            first = not self.failed
            self.failed = True
            return first


class ReplicaSeismicServer(AsyncSeismicServer):
    """Micro-batching server with N replica workers behind one queue.

    Parameters (on top of ``AsyncSeismicServer``'s)
    ----------
    index           ``mode="mirror"``: one ``SeismicIndex`` shared by
                    every replica. ``mode="shard"``: the stacked pytree
                    from ``build_sharded_index`` (leading axis = shard).
    n_replicas      worker count. Required for mirror; defaults to the
                    stacked leading axis for shard (must match if
                    given).
    mode            ``mirror`` | ``shard`` (see module docstring).
    balancer        routing policy; default
                    ``StageTimingBalancer(n_replicas)``. Mirror mode
                    routes each batch through ``balancer.pick()``;
                    shard mode fans out but still feeds per-replica
                    timings for the rollup gauges.
    replica_delay_s artificial per-launch latency: scalar (uniform) or
                    one value per replica.
    n_docs          live corpus size for shard mode (pre-padding
                    ``docs.n``); bounds globalized ids at the merge.
                    Defaults to ``n_replicas * per_shard`` — the
                    content-based pad mask still applies either way.
    mailbox_depth   per-replica dispatch buffer; a full mailbox
                    backpressures the dispatcher (and, via vtime, the
                    balancer already steers away from slow replicas).

    ``stage_timing`` (and sampled staged launches) are mirror-mode
    features: shard-mode launches run the fused pipeline per shard and
    attach no stage spans to the merged trace.
    """

    def __init__(self, index, params: SearchParams, *,
                 n_replicas: int | None = None, mode: str = "mirror",
                 balancer: StageTimingBalancer | None = None,
                 replica_delay_s=None, n_docs: int | None = None,
                 mailbox_depth: int = 8, **kw):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; choose from {MODES}")
        if mode == "mirror":
            if n_replicas is None or n_replicas < 1:
                raise ValueError("mirror mode needs n_replicas >= 1")
            shards = None
            representative = index
        else:
            n_shards = jax.tree.leaves(index)[0].shape[0]
            if n_replicas is None:
                n_replicas = n_shards
            elif n_replicas != n_shards:
                raise ValueError(
                    f"n_replicas={n_replicas} != stacked index shards "
                    f"{n_shards}")
            if kw.get("stage_timing"):
                raise ValueError("stage_timing is mirror-mode only; "
                                 "shard launches run fused per shard")
            shards = shard_views(index)
            representative = _unstacked_shape(shards[0])
        self.mode = mode
        self.n_replicas = n_replicas
        self.mailbox_depth = mailbox_depth
        super().__init__(representative, params, **kw)
        if mode == "shard":
            # shard launches are always fused; drop the staged program
            self._fns = None
            self.per_shard = representative.fwd.coords.shape[0]
            self.n_docs = n_docs if n_docs is not None \
                else n_replicas * self.per_shard
            self._replicas = [(view, None) for view in shards]
            k, nd = self.params.k, self.n_docs
            self._merge = jax.jit(
                lambda cand, scores: merge_topk(cand, scores, k, nd))
        else:
            self.n_docs = n_docs
            self._replicas = [(self.index, self._fns)] * n_replicas
        self.balancer = balancer if balancer is not None \
            else StageTimingBalancer(n_replicas)
        if self.balancer.n_replicas != n_replicas:
            raise ValueError(
                f"balancer covers {self.balancer.n_replicas} replicas, "
                f"server has {n_replicas}")
        if replica_delay_s is None:
            self._delay = [0.0] * n_replicas
        elif np.isscalar(replica_delay_s):
            self._delay = [float(replica_delay_s)] * n_replicas
        else:
            self._delay = [float(d) for d in replica_delay_s]
            if len(self._delay) != n_replicas:
                raise ValueError(
                    f"replica_delay_s has {len(self._delay)} entries "
                    f"for {n_replicas} replicas")
        self._mailboxes: list[_queue.Queue] = []
        self._replica_threads: list[threading.Thread] = []
        self._register_replica_gauges()

    # ------------------------------------------------------ observability

    def _register_replica_gauges(self) -> None:
        reg = self.telemetry.registry
        self._replica_dispatches = reg.counter(
            "seismic_replica_dispatches_total",
            "Micro-batches dispatched to each replica", ("replica",))
        cost_g = reg.gauge(
            "seismic_replica_cost_ewma_seconds",
            "Balancer EWMA launch cost per replica", ("replica",))
        share_g = reg.gauge(
            "seismic_replica_dispatch_share",
            "Fraction of dispatches routed to each replica", ("replica",))
        inflight_g = reg.gauge(
            "seismic_replica_inflight",
            "Dispatches not yet acknowledged per replica", ("replica",))
        self._replica_stage_g = reg.gauge(
            "seismic_replica_stage_seconds",
            "EWMA per-stage seconds per replica (staged launches)",
            ("replica", "stage"))
        for rid in range(self.n_replicas):
            cost_g.labels(str(rid)).set_fn(
                lambda rid=rid: self.balancer.cost(rid))
            share_g.labels(str(rid)).set_fn(
                lambda rid=rid: self.balancer.snapshot()
                ["dispatch_share"][rid])
            inflight_g.labels(str(rid)).set_fn(
                lambda rid=rid: self.balancer.snapshot()["inflight"][rid])

    def _on_timing(self, rid: int, seconds: float,
                   stage_seconds: dict[str, float]) -> None:
        """Per-launch feedback from a replica worker into the balancer
        and the per-replica gauges."""
        self.balancer.record(rid, seconds, stage_seconds or None)
        if stage_seconds:
            rollup = self.balancer.snapshot()["stage_cost_ewma_s"][rid]
            for name, ewma in rollup.items():
                if not name.startswith("refine_round_"):
                    self._replica_stage_g.labels(str(rid), name).set(ewma)

    # ------------------------------------------------------- lifecycle

    def start(self, warmup: bool = True) -> "ReplicaSeismicServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        if self.queue.closed:
            raise RuntimeError("server was stopped; its queue is closed "
                               "— build a new ReplicaSeismicServer")
        self._mailboxes = [_queue.Queue(maxsize=self.mailbox_depth)
                           for _ in range(self.n_replicas)]
        self._replica_threads = [
            threading.Thread(target=self._replica_loop, args=(rid,),
                             name=f"seismic-replica-{rid}", daemon=True)
            for rid in range(self.n_replicas)]
        for t in self._replica_threads:
            t.start()
        return super().start(warmup=warmup)

    def warmup(self) -> None:
        if self.mode == "mirror":
            super().warmup()
            return
        self._warmup_shards(self._replicas, self.params, self.n_docs)
        self._warmup_merge(self._merge, self.params.k)

    def _warmup_shards(self, replicas, params, n_docs) -> None:
        """Compile every ladder width on every shard's device (a
        program is compiled per device it runs on; one thread per
        shard so the compiles overlap)."""
        def warm(view):
            for width in self.launch_widths:
                coords = jnp.zeros((width, self.query_nnz), jnp.int32)
                vals = jnp.zeros((width, self.query_nnz), jnp.float32)
                jax.block_until_ready(
                    search_shard(view, coords, vals, 0, params, n_docs))

        with ThreadPoolExecutor(len(replicas)) as pool:
            list(pool.map(warm, [view for view, _ in replicas]))

    def _warmup_merge(self, merge, k: int) -> None:
        for width in self.launch_widths:
            cand = jnp.full((width, self.n_replicas * k), -1,
                            jnp.int32)
            scores = jnp.full((width, self.n_replicas * k),
                              -jnp.inf, jnp.float32)
            jax.block_until_ready(merge(cand, scores))

    # ----------------------------------------------------- index swap

    def _publish_swap(self, index, params, fns) -> None:
        super()._publish_swap(index, params, fns)
        if self.mode == "mirror":
            # republish the mirror list wholesale; replica loops
            # re-read it per item, so the next batch on every replica
            # serves the new generation
            self._replicas = [(self.index, self._fns)] * self.n_replicas

    def swap_index(self, index, params: SearchParams | None = None, *,
                   warmup: bool = True, n_docs: int | None = None) -> int:
        """Mirror mode: identical to ``AsyncSeismicServer.swap_index``
        (every replica flips to the new index on its next batch). Shard
        mode: ``index`` is a new ``build_sharded_index`` stacked pytree
        with the SAME shard count; per-shard state (slices, globalize
        offsets, merge program) is republished atomically, and in-flight
        shard jobs finish on their dispatch-time view."""
        if self.mode == "mirror":
            return super().swap_index(index, params, warmup=warmup)
        params = self.params if params is None else params
        n_shards = jax.tree.leaves(index)[0].shape[0]
        if n_shards != self.n_replicas:
            raise ValueError(
                f"stacked index has {n_shards} shards; server has "
                f"{self.n_replicas} replicas (shard swap cannot resize)")
        shards = shard_views(index)
        rep = _unstacked_shape(shards[0])
        from repro.graph.refine import validate_refine_params
        from repro.tune.policy import validate_tuned_index
        validate_refine_params(rep, params)
        validate_tuned_index(rep)
        per_shard = rep.fwd.coords.shape[0]
        nd = n_docs if n_docs is not None else n_shards * per_shard
        k = params.k
        merge = jax.jit(
            lambda cand, scores: merge_topk(cand, scores, k, nd))
        replicas = [(view, None) for view in shards]
        if warmup:
            self._warmup_shards(replicas, params, nd)
            self._warmup_merge(merge, k)
        with self._swap_lock:
            self._publish_swap(rep, params, None)
            self.per_shard = per_shard
            self.n_docs = nd
            self._merge = merge
            self._replicas = replicas
            epoch = self.epoch
        self._register_gauges()
        self.telemetry.inc("swaps")
        return epoch

    # ---------------------------------------------------------- worker

    def _worker(self) -> None:
        """Dispatcher: pull micro-batches off the ONE queue, route to
        replica mailboxes; on shutdown drain, send sentinels, join."""
        try:
            while True:
                batch = self._next_batch()
                if batch is None:
                    return
                try:
                    if self.mode == "mirror":
                        rid = self.balancer.pick()
                        self._replica_dispatches.labels(str(rid)).inc()
                        self._mailboxes[rid].put(batch)
                    else:
                        self._dispatch_shard_job(batch)
                except Exception as e:   # noqa: BLE001 — fail batch, keep routing
                    for r in batch:
                        self._fail_all(r, f"error: {type(e).__name__}: {e}")
        finally:
            for box in self._mailboxes:
                box.put(None)
            for t in self._replica_threads:
                t.join()
            self._replica_threads = []

    def _dispatch_shard_job(self, batch: list[Request]) -> None:
        tel = self.telemetry
        n = len(batch)
        width = self._pick_width(n)
        tel.inc(f"launch_width_{width}")
        tel.inc("dispatched", n)
        coords, vals = self._pack(batch, width)
        with self._swap_lock:
            view = (self._replicas, self.per_shard, self.n_docs,
                    self._merge)
        job = _ShardJob(batch, coords, vals, width, self._next_seq(),
                        time.monotonic(), self.n_replicas, view)
        for rid, box in enumerate(self._mailboxes):
            self._replica_dispatches.labels(str(rid)).inc()
            box.put(job)

    def _replica_loop(self, rid: int) -> None:
        delay = self._delay[rid]
        while True:
            item = self._mailboxes[rid].get()
            if item is None:
                return
            # re-read the replica's (index, fns) for EVERY item: the
            # list object is republished wholesale by swap_index, so a
            # mirror replica picks up a swapped index on its next batch
            # instead of serving the retired generation forever
            index, fns = self._replicas[rid]
            try:
                if isinstance(item, _ShardJob):
                    self._run_shard_part(rid, item)
                else:
                    self._launch(
                        item, index=index, fns=fns, delay_s=delay,
                        span_attrs={"replica": rid},
                        on_timing=lambda s, st, rid=rid:
                            self._on_timing(rid, s, st))
            except Exception as e:   # noqa: BLE001 — fail batch, keep serving
                status = f"error: {type(e).__name__}: {e}"
                if isinstance(item, _ShardJob):
                    if item.fail():
                        for r in item.batch:
                            self._fail_all(r, status)
                else:
                    for r in item:
                        self._fail_all(r, status)

    # ------------------------------------------------------ shard mode

    def _run_shard_part(self, rid: int, job: _ShardJob) -> None:
        """Score one shard, globalize + pad-mask its top-k, deposit;
        the last shard in merges and fulfils the whole batch. All shard
        state comes from the job's dispatch-time view, never ``self``
        (see ``_ShardJob.view``)."""
        replicas, per_shard, n_docs, _ = job.view
        view, _ = replicas[rid]
        t0 = time.monotonic()
        if self._delay[rid] > 0.0:
            time.sleep(self._delay[rid])
        # runs on the device holding shard rid; pad hits are masked to
        # (-inf, -1) inside, before anything crosses the shard boundary
        m_scores, m_gids, ev = jax.block_until_ready(search_shard(
            view, jnp.asarray(job.coords), jnp.asarray(job.vals),
            rid * per_shard, self.params, n_docs))
        t1 = time.monotonic()
        self._on_timing(rid, t1 - t0, {})
        part = (np.asarray(m_gids), np.asarray(m_scores), np.asarray(ev))
        if job.add(rid, part, t0, t1):
            self._finish_shard_job(job)

    def _finish_shard_job(self, job: _ShardJob) -> None:
        tel = self.telemetry
        n = len(job.batch)
        parts = [job.parts[r] for r in range(self.n_replicas)]
        all_g = np.concatenate([p[0] for p in parts], axis=1)
        all_s = np.concatenate([p[1] for p in parts], axis=1)
        merge = job.view[3]
        top_s, top_ids, _ = merge(jnp.asarray(all_g),
                                  jnp.asarray(all_s))
        # docs_evaluated is the total exactly-scored docs ACROSS shards
        ev = np.sum([p[2] for p in parts], axis=0)
        top_ids = np.asarray(top_ids)
        top_s = np.asarray(top_s)
        t1 = time.monotonic()
        tel.record_latency("launch", t1 - job.t0_min)
        self._account(n, job.width, ev)
        # shard-mode audits are recall-only (no funnel captures: shard
        # launches run fused, and memberships are per-shard anyway);
        # the auditor must be built over the FULL corpus index so its
        # oracle sees the same doc-id space as the merged top-k
        audit_span = None
        if self.auditor is not None:
            rows = self.auditor.plan(n)
            if rows:
                a0 = time.monotonic()
                for i in rows:
                    self.auditor.feed(job.coords[i], job.vals[i],
                                      top_ids[i], captures=None, row=i)
                audit_span = (a0, time.monotonic())
        self._fulfil(job.batch, top_ids, top_s, ev,
                     dispatch_t=job.dispatch_t, t1=t1, width=job.width,
                     seq=job.seq, staged=False,
                     span_attrs={"replica": "shard-merge",
                                 "n_shards": self.n_replicas},
                     audit_span=audit_span)


def _unstacked_shape(view):
    """Shape-only stand-in for one shard's unstacked index: what the
    base server validates and sizes from, without copying the shard."""
    return jax.eval_shape(lambda v: jax.tree.map(lambda x: x[0], v), view)
