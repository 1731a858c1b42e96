"""Stage 2 — router: quantized summary scoring (paper phase R).

Two routed paths behind ``SearchParams.superblock_fanout``:

* **flat** (``superblock_fanout == 0``, the default): scores EVERY
  summary of every probed list for the whole query batch in one shot —
  the flattened (probed list, block) axis has length ``cut * n_blocks``
  and the result is ``r [Q, cut * n_blocks]`` with dead blocks at
  -inf.
* **hierarchical** (``superblock_fanout > 0``, requires an index built
  with the matching ``SeismicConfig.superblock_fanout``): a BMP-style
  two-stage route. Stage A scores the coarse superblock tier
  (``cut * n_superblocks`` summaries, each upper-bounding its
  children); stage B keeps the top ``superblock_budget`` superblocks
  per query and scores ONLY their children's block summaries
  (``superblock_budget * fanout`` dots), scattering the scores back
  into the flat ``[Q, cut * n_blocks]`` layout with pruned blocks at
  -inf. Selector policies consume the result unchanged. Router work
  drops from ``cut * n_blocks`` to
  ``cut * n_superblocks + superblock_budget * fanout`` summary dots
  per query (:func:`router_work`).

With the kernels on (``use_kernel``; ``None``, the default, turns
them on on a TPU) both tiers use the batched summary_dot Pallas
kernel (u8 dequant fused) — the identical kernel, just different
summary arrays.

With ``fuse_level >= 2`` the whole route collapses into ONE fused
Pallas launch per tier (:mod:`repro.kernels.router_fused`): the
host-side summary gathers (``index.sum_coords[lists]`` and, for the
hierarchical path, the ``[Q, M, f, S]`` child-summary gather between
stage A and stage B) move inside the kernel and never touch HBM. Only
the hierarchical scatter back into the flat layout stays on the host —
it is output-sized, not summary-sized. Results are bit-exact with the
unfused path (parity tests pin it).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.kernels.runtime import default_use_kernel
from repro.retrieval.params import SearchParams
from repro.sparse.ops import PaddedSparse
from repro.sparse.quant import dequantize_u8

if TYPE_CHECKING:  # annotation-only: keeps repro.retrieval import-cycle-free
    from repro.core.types import SeismicIndex

NEG = -jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoutedBatch:
    """Everything the selector and scorer stages need, batched."""

    q_dense: jax.Array   # f32 [Q, d]
    lists: jax.Array     # i32 [Q, cut]     probed coordinate per slot
    r: jax.Array         # f32 [Q, cut*nb]  block summary scores (-inf dead)
    q: PaddedSparse | None = None   # the padded-sparse query the kernels
    #                                 score from (None: they fall back
    #                                 to the dense rows, exact but slow)


def _summary_scores(q_dense, sc, sq, scale, zero, use_kernel, q=None):
    """<q, dequant(summary)> over a flat [Q, L, S] summary axis."""
    if default_use_kernel(use_kernel):
        from repro.kernels.summary_dot.ops import summary_dot_batch
        return summary_dot_batch(q_dense if q is None else q, sc, sq,
                                 scale, zero)
    qn = sc.shape[0]
    sv = dequantize_u8(sq, scale, zero)
    gathered = jnp.take_along_axis(
        q_dense, sc.reshape(qn, -1), axis=1).reshape(sc.shape)
    return (gathered * sv).sum(axis=-1)


def _route_flat(index: SeismicIndex, q_dense: jax.Array, lists: jax.Array,
                p: SearchParams, q: PaddedSparse | None) -> RoutedBatch:
    """Summary inner products for all blocks of the probed lists."""
    if p.fuse_level >= 2:
        from repro.kernels.router_fused import router_flat_batch
        r = router_flat_batch(lists, q_dense, index.sum_coords,
                              index.sum_q, index.sum_scale,
                              index.sum_zero, index.block_len)
        return RoutedBatch(q_dense=q_dense, lists=lists, r=r, q=q)
    qn, cut = lists.shape
    nb = index.config.n_blocks
    s = index.sum_coords.shape[-1]
    sc = index.sum_coords[lists].reshape(qn, cut * nb, s)   # [Q, L, S]
    sq = index.sum_q[lists].reshape(qn, cut * nb, s)
    scale = index.sum_scale[lists].reshape(qn, cut * nb)
    zero = index.sum_zero[lists].reshape(qn, cut * nb)
    r = _summary_scores(q_dense, sc, sq, scale, zero, p.use_kernel, q)
    alive = (index.block_len[lists] > 0).reshape(qn, cut * nb)
    r = jnp.where(alive, r, NEG)
    return RoutedBatch(q_dense=q_dense, lists=lists, r=r, q=q)


def _route_hierarchical(index: SeismicIndex, q_dense: jax.Array,
                        lists: jax.Array, p: SearchParams,
                        q: PaddedSparse | None) -> RoutedBatch:
    """Superblock tier -> survivors -> child block summaries.

    Pruning is justified by upper bounds: a block is pruned only when
    its superblock's score (>= the block's own summary score) misses
    the per-query top ``superblock_budget``, so every pruned block
    scores at most the weakest kept superblock.
    """
    qn, cut = lists.shape
    cfg = index.config
    nb, f, ns = cfg.n_blocks, cfg.superblock_fanout, cfg.n_superblocks
    if p.fuse_level >= 2:
        # one launch for stage A + top-M + child gather + stage B; the
        # host keeps only the output-sized scatter below
        from repro.kernels.router_fused import router_hier_batch
        m = min(p.superblock_budget, cut * ns)
        rb, flat = router_hier_batch(
            lists, q_dense, index.sup_coords, index.sup_q,
            index.sup_scale, index.sup_zero, index.sum_coords,
            index.sum_q, index.sum_scale, index.sum_zero,
            index.block_len, m=m, fanout=f)
        r = jnp.full((qn, cut * nb), NEG, q_dense.dtype)
        r = r.at[jnp.arange(qn)[:, None], flat].max(rb)
        return RoutedBatch(q_dense=q_dense, lists=lists, r=r, q=q)
    s2 = index.sup_coords.shape[-1]
    # ---- stage A: coarse tier, one batched summary_dot over cut * ns
    sc = index.sup_coords[lists].reshape(qn, cut * ns, s2)
    sq = index.sup_q[lists].reshape(qn, cut * ns, s2)
    scale = index.sup_scale[lists].reshape(qn, cut * ns)
    zero = index.sup_zero[lists].reshape(qn, cut * ns)
    u = _summary_scores(q_dense, sc, sq, scale, zero, p.use_kernel, q)
    # a superblock is alive iff any child block is (all-padding -> -inf)
    blk_alive = jnp.pad(index.block_len > 0, ((0, 0), (0, (-nb) % f)))
    sup_alive = blk_alive.reshape(-1, ns, f).any(-1)        # [L, ns]
    u = jnp.where(sup_alive[lists].reshape(qn, cut * ns), u, NEG)
    # ---- stage B: children of the top-M superblocks only
    m = min(p.superblock_budget, cut * ns)
    us, sup_ids = jax.lax.top_k(u, m)                       # [Q, M]
    li = sup_ids // ns                                      # probed slot
    gi = sup_ids % ns                                       # group in list
    child = gi[..., None] * f + jnp.arange(f)               # [Q, M, f]
    in_range = child < nb
    child = jnp.minimum(child, nb - 1)
    coord = jnp.take_along_axis(lists, li, axis=1)          # [Q, M]
    bsc = index.sum_coords[coord[..., None], child]         # [Q, M, f, S]
    bsq = index.sum_q[coord[..., None], child]
    bscale = index.sum_scale[coord[..., None], child]
    bzero = index.sum_zero[coord[..., None], child]
    s = bsc.shape[-1]
    rb = _summary_scores(q_dense, bsc.reshape(qn, m * f, s),
                         bsq.reshape(qn, m * f, s),
                         bscale.reshape(qn, m * f),
                         bzero.reshape(qn, m * f), p.use_kernel, q)
    alive = (in_range
             & (index.block_len[coord[..., None], child] > 0)
             & jnp.isfinite(us)[..., None])                 # [Q, M, f]
    rb = jnp.where(alive.reshape(qn, m * f), rb, NEG)
    # ---- scatter back into the flat (probed slot, block) layout
    flat = (li[..., None] * nb + child).reshape(qn, m * f)
    r = jnp.full((qn, cut * nb), NEG, q_dense.dtype)
    r = r.at[jnp.arange(qn)[:, None], flat].max(rb)
    return RoutedBatch(q_dense=q_dense, lists=lists, r=r, q=q)


def route_batch(index: SeismicIndex, q_dense: jax.Array, lists: jax.Array,
                p: SearchParams, q: PaddedSparse | None = None
                ) -> RoutedBatch:
    """Phase R for the whole batch; flat or hierarchical per
    ``p.superblock_fanout`` (0 = flat, bit-exact with the single-tier
    router). ``q`` is the padded-sparse form of ``q_dense``, which the
    kernel path scores from; it rides on the batch to the scorer."""
    if p.superblock_fanout <= 0:
        return _route_flat(index, q_dense, lists, p, q)
    if index.sup_coords is None:
        raise ValueError(
            "hierarchical routing requested (superblock_fanout="
            f"{p.superblock_fanout}) but the index has no superblock "
            "tier; build with SeismicConfig(superblock_fanout > 0)")
    if index.config.superblock_fanout != p.superblock_fanout:
        raise ValueError(
            f"superblock_fanout mismatch: SearchParams has "
            f"{p.superblock_fanout}, index was built with "
            f"{index.config.superblock_fanout}")
    return _route_hierarchical(index, q_dense, lists, p, q)


def router_work(cfg, p: SearchParams) -> int:
    """Summary inner products the router evaluates per query — the
    phase-R work metric (flat: ``cut * n_blocks``; hierarchical:
    ``cut * n_superblocks + superblock_budget * fanout``)."""
    if p.superblock_fanout <= 0:
        return p.cut * cfg.n_blocks
    coarse = p.cut * cfg.n_superblocks
    return coarse + min(p.superblock_budget, coarse) * p.superblock_fanout
