"""Pipeline orchestration: prep -> router -> selector -> scorer ->
merge -> refine.

``run_pipeline`` is the traceable batch-first core shared by every
execution surface (local search_batch, SeismicServer, the distributed
shard_map search); ``search_pipeline`` is its jitted front door.
``stage_fns`` / ``run_pipeline_staged`` expose the same pipeline as
six standalone-jitted stages for per-stage latency attribution (the
serving telemetry and the stage-throughput benchmark both hook here).

The sixth stage (refine — kNN-graph neighbor expansion, see
``repro.graph``) is gated on ``SearchParams.graph_degree`` /
``refine_rounds``; with either at 0 it traces as the identity, so the
five-stage program of earlier revisions is reproduced bit-exactly.
"""
from __future__ import annotations

import time
from functools import partial
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp

from repro.graph.refine import refine_batch
from repro.retrieval.merge import merge_topk
from repro.retrieval.params import SearchParams
from repro.retrieval.prep import prep_queries
from repro.retrieval.router import route_batch
from repro.retrieval.scorer import score_selection
from repro.retrieval.selector import get_selector
from repro.sparse.ops import PaddedSparse

if TYPE_CHECKING:  # annotation-only: keeps repro.retrieval import-cycle-free
    from repro.core.types import SeismicIndex


def run_pipeline(index: SeismicIndex, q_coords: jax.Array,
                 q_vals: jax.Array, p: SearchParams
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched staged search over padded-sparse queries [Q, nnz].

    Returns (scores [Q, k], ids [Q, k] with -1 padding,
    docs_evaluated [Q]). Traceable: safe inside jit / shard_map.
    """
    select = get_selector(p.policy)                 # static under jit
    q_dense, lists, _ = prep_queries(q_coords, q_vals, index.dim, p.cut)
    q = _sparse_query(index, q_coords, q_vals)
    batch = route_batch(index, q_dense, lists, p, q)
    sel = select(index, batch, p)
    cand, scores = score_selection(index, batch, sel, p.use_kernel,
                                   fuse_level=p.fuse_level)
    top_s, top_ids, ev = merge_topk(cand, scores, p.k, index.n_docs)
    return refine_batch(index, q_dense, top_s, top_ids, ev, p, q)


def _sparse_query(index: SeismicIndex, q_coords, q_vals) -> PaddedSparse:
    """The query batch as the kernels score it (f32 values, like the
    dense rows ``prep_queries`` builds)."""
    return PaddedSparse(q_coords.astype(jnp.int32),
                        q_vals.astype(jnp.float32), index.dim)


@partial(jax.jit, static_argnames=("p",))
def search_pipeline(index: SeismicIndex, queries: PaddedSparse,
                    p: SearchParams):
    """Jitted batched Seismic search (the shared execution path).

    Returns (scores [Q,k], ids [Q,k] with -1 padding, docs_evaluated [Q]).
    """
    return run_pipeline(index, queries.coords, queries.vals, p)


STAGES = ("prep", "router", "selector", "scorer", "merge", "refine")


def stage_fns(index: SeismicIndex, p: SearchParams
              ) -> dict[str, Callable]:
    """Standalone-jitted stage functions (index and params closed over).

    These are the per-stage timing hooks: each stage compiles on its
    own so a caller can ``block_until_ready`` between stages and
    attribute wall time, at the cost of materializing inter-stage
    arrays (slightly slower end-to-end than the fused
    ``search_pipeline``). Keyed by ``STAGES`` name, plus
    ``refine_round`` — a single refine round for the traced path's
    per-round child spans (compiled lazily, one program per widening
    ``scored`` shape).
    """
    from repro.graph.refine import refine_one_round
    select = get_selector(p.policy)
    return {
        "prep": jax.jit(
            lambda c, v: prep_queries(c, v, index.dim, p.cut)),
        "router": jax.jit(
            lambda qd, ls, q=None: route_batch(index, qd, ls, p, q)),
        "selector": jax.jit(lambda b: select(index, b, p)),
        "scorer": jax.jit(
            lambda b, s: score_selection(index, b, s, p.use_kernel,
                                         fuse_level=p.fuse_level)),
        "merge": jax.jit(lambda c, s: merge_topk(c, s, p.k, index.n_docs)),
        "refine": jax.jit(
            lambda qd, s, i, e, q=None: refine_batch(index, qd, s, i, e,
                                                     p, q)),
        "refine_round": jax.jit(
            lambda qd, s, i, e, sc, q=None: refine_one_round(
                index, qd, s, i, e, sc, p, q)),
    }


def run_pipeline_staged(index: SeismicIndex, q_coords: jax.Array,
                        q_vals: jax.Array, p: SearchParams,
                        fns: dict[str, Callable] | None = None,
                        record: Callable[[str, float], None] | None = None,
                        span_cb: Callable[[str, float, float], None]
                        | None = None,
                        split_refine: bool = False,
                        probe: Callable[[str, object], None] | None = None,
                        audit: bool = False
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stage-by-stage pipeline with per-stage wall-time reporting.

    ``record(stage_name, seconds)`` is called once per stage with the
    blocking wall time; ``span_cb(stage_name, t0, t1)`` additionally
    receives the ``time.monotonic`` start/end stamps (the tracer hook).
    With ``split_refine`` the refine stage runs round-by-round and
    ``refine_round_<j>`` intervals are reported to ``span_cb`` (nested
    inside the ``refine`` interval) — identical results, one extra jit
    boundary per round. ``probe(name, value)`` exposes chosen
    intermediates (``("cand", scorer candidate ids)``) to device
    accounting without changing any dataflow; with ``audit`` the probe
    additionally receives the per-stage membership captures the
    quality-plane loss funnel attributes misses from — ``lists``
    (probed coordinates), ``router_r`` (flat block summary scores,
    -inf = unrouted), and ``merge_ids`` (pre-refine merged top-k).
    Pass a prebuilt ``fns`` (from ``stage_fns``) to reuse compiled
    stages across calls; fixed input shapes never recompile. Output
    matches ``search_pipeline``.
    """
    if fns is None:
        fns = stage_fns(index, p)

    def timed(name, fn, *args):
        t0 = time.monotonic()
        out = jax.block_until_ready(fn(*args))
        t1 = time.monotonic()
        if record is not None:
            record(name, t1 - t0)
        if span_cb is not None:
            span_cb(name, t0, t1)
        return out

    q_dense, lists, _ = timed("prep", fns["prep"], q_coords, q_vals)
    q = _sparse_query(index, q_coords, q_vals)
    batch = timed("router", fns["router"], q_dense, lists, q)
    sel = timed("selector", fns["selector"], batch)
    cand, scores = timed("scorer", fns["scorer"], batch, sel)
    if probe is not None:
        probe("cand", cand)
        if audit:
            probe("lists", lists)
            probe("router_r", batch.r)
    top_s, top_ids, ev = timed("merge", fns["merge"], cand, scores)
    if audit and probe is not None:
        probe("merge_ids", top_ids)
    if not (split_refine and p.refine_rounds > 0 and p.graph_degree > 0):
        return timed("refine", fns["refine"], q_dense, top_s, top_ids, ev,
                     q)
    # round-by-round refine: same ops as refine_batch, one jit boundary
    # per round so each round's wall time is attributable
    from repro.graph.refine import scored_init, validate_refine_params
    validate_refine_params(index, p)
    t0 = time.monotonic()
    scored = scored_init(top_ids, index.n_docs)
    s, i, e = top_s, top_ids, ev
    for j in range(p.refine_rounds):
        s, i, e, scored = timed(f"refine_round_{j}", fns["refine_round"],
                                q_dense, s, i, e, scored, q)
    t1 = time.monotonic()
    if record is not None:
        record("refine", t1 - t0)
    if span_cb is not None:
        span_cb("refine", t0, t1)
    return s, i, e
