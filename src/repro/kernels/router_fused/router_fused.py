"""Pallas kernels: the FUSED router (Seismic phase R in one launch).

The staged router pays two HBM round-trips that this kernel family
removes:

* flat routing materializes the probed summaries host-side
  (``index.sum_coords[lists]`` -> [Q, cut*nb, S] + the u8/scale/zero
  planes) before the summary_dot launch;
* hierarchical routing additionally gathers the child summaries of the
  surviving superblocks ([Q, M, f, S] int32 + u8 + 2 f32 planes)
  between its stage-A and stage-B summary_dot launches, plus a
  separate top-M launch in between.

Here the kernel receives the probed coordinate ids ``lists [Q, cut]``
and the per-list summary planes, and performs stage A, the per-query
top-M superblock selection, the child-summary gather, and stage B in
ONE launch — per-query intermediates never leave VMEM. Outputs are the
tiny per-query results only (flat: the routed scores; hierarchical:
child scores + their flat positions for the host-side scatter, which
is [Q, M*f] — the one intermediate that is output-sized, not
summary-sized).

Math is op-for-op identical to the unfused path (same dequant formula,
same -inf masking, same top_k), so ``fuse_level=2`` is bit-exact with
``fuse_level=0`` — the parity tests pin it.

Coverage boundary (see src/repro/kernels/README.md): the summary
planes ride in whole-array blocks, exact under interpret mode (CPU
CI). The Mosaic lowering additionally needs the planes VMEM-resident
(fine for per-list tiers at paper scale) or an ANY-space DMA variant,
and in-kernel ``top_k`` support; real-TPU validation is the
ROADMAP-tracked follow-on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -jnp.inf


def _summary_scores(q, coords, u8, scale, zero):
    """<q_row, dequant(summary)> for [tq, L, S] summaries — the same
    fused-dequant inner product as the summary_dot kernel."""
    tq, l, s = coords.shape
    gathered = jnp.take_along_axis(
        q, coords.reshape(tq, l * s), axis=1).reshape(tq, l, s)
    u8f = u8.astype(q.dtype)
    deq = (u8f - 1.0) * scale[..., None].astype(q.dtype) \
        + zero[..., None].astype(q.dtype)
    deq = jnp.where(u8 > 0, deq, 0.0)           # level 0 == padding
    return (gathered * deq).sum(axis=-1)


def _take_rows(plane, lists):
    """plane [L, ...] indexed by lists [tq, cut] -> [tq, cut, ...]."""
    return jnp.take(plane, lists, axis=0, mode="clip")


def _router_flat_kernel(lists_ref, q_ref, sumc_ref, sumq_ref, sums_ref,
                        sumz_ref, blen_ref, r_ref):
    lists = lists_ref[...]                      # [tq, cut]
    q = q_ref[...]                              # [tq, d]
    tq, cut = lists.shape
    nb = blen_ref.shape[1]
    s = sumc_ref.shape[2]
    sc = _take_rows(sumc_ref[...], lists).reshape(tq, cut * nb, s)
    sq = _take_rows(sumq_ref[...], lists).reshape(tq, cut * nb, s)
    scale = _take_rows(sums_ref[...], lists).reshape(tq, cut * nb)
    zero = _take_rows(sumz_ref[...], lists).reshape(tq, cut * nb)
    r = _summary_scores(q, sc, sq, scale, zero)
    alive = (_take_rows(blen_ref[...], lists) > 0).reshape(tq, cut * nb)
    r_ref[...] = jnp.where(alive, r, NEG)


@functools.partial(jax.jit, static_argnames=("tile_q", "interpret"))
def router_flat_pallas(lists: jax.Array, q_dense: jax.Array,
                       sum_coords: jax.Array, sum_q: jax.Array,
                       sum_scale: jax.Array, sum_zero: jax.Array,
                       block_len: jax.Array, *, tile_q: int = 8,
                       interpret: bool = True) -> jax.Array:
    """Fused flat route: probed lists [Q, cut] + summary planes
    [L, nb, S] -> routed scores r [Q, cut*nb] (-inf dead), one launch.
    Q must be a multiple of tile_q (ops.py pads)."""
    qn, cut = lists.shape
    l, nb, s = sum_coords.shape
    d = q_dense.shape[1]
    assert q_dense.shape[0] == qn and qn % tile_q == 0, (
        q_dense.shape, lists.shape, tile_q)
    grid = (qn // tile_q,)
    full3 = pl.BlockSpec((l, nb, s), lambda i: (0, 0, 0))
    full2 = pl.BlockSpec((l, nb), lambda i: (0, 0))
    return pl.pallas_call(
        _router_flat_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, cut), lambda i: (i, 0)),
            pl.BlockSpec((tile_q, d), lambda i: (i, 0)),
            full3, full3, full2, full2, full2,
        ],
        out_specs=pl.BlockSpec((tile_q, cut * nb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, cut * nb), q_dense.dtype),
        name="router_flat",
        interpret=interpret,
    )(lists, q_dense, sum_coords, sum_q, sum_scale, sum_zero, block_len)


def _router_hier_kernel(lists_ref, q_ref, supc_ref, supq_ref, sups_ref,
                        supz_ref, sumc_ref, sumq_ref, sums_ref, sumz_ref,
                        blen_ref, rb_ref, flat_ref, *, m, fanout):
    lists = lists_ref[...]                      # [tq, cut]
    q = q_ref[...]                              # [tq, d]
    tq, cut = lists.shape
    l, ns, s2 = supc_ref.shape
    nb = blen_ref.shape[1]
    s = sumc_ref.shape[2]
    blen = blen_ref[...]                        # [L, nb]
    # ---- stage A: coarse superblock tier for the probed lists
    sc = _take_rows(supc_ref[...], lists).reshape(tq, cut * ns, s2)
    sq = _take_rows(supq_ref[...], lists).reshape(tq, cut * ns, s2)
    sscale = _take_rows(sups_ref[...], lists).reshape(tq, cut * ns)
    szero = _take_rows(supz_ref[...], lists).reshape(tq, cut * ns)
    u = _summary_scores(q, sc, sq, sscale, szero)
    # a superblock is alive iff any child block is (all-padding -> -inf)
    blk_alive = jnp.pad(blen > 0, ((0, 0), (0, (-nb) % fanout)))
    sup_alive = blk_alive.reshape(l, ns, fanout).any(-1)
    u = jnp.where(_take_rows(sup_alive, lists).reshape(tq, cut * ns),
                  u, NEG)
    # ---- per-query top-M superblocks, child gather, stage B — all VMEM
    us, sup_ids = jax.lax.top_k(u, m)           # [tq, M]
    li = sup_ids // ns                          # probed slot
    gi = sup_ids % ns                           # group in list
    child = gi[..., None] * fanout + jnp.arange(fanout)     # [tq, M, f]
    in_range = child < nb
    child = jnp.minimum(child, nb - 1)
    coord = jnp.take_along_axis(lists, li, axis=1)          # [tq, M]
    bsc = sumc_ref[...][coord[..., None], child]            # [tq, M, f, S]
    bsq = sumq_ref[...][coord[..., None], child]
    bscale = sums_ref[...][coord[..., None], child]
    bzero = sumz_ref[...][coord[..., None], child]
    rb = _summary_scores(q, bsc.reshape(tq, m * fanout, s),
                         bsq.reshape(tq, m * fanout, s),
                         bscale.reshape(tq, m * fanout),
                         bzero.reshape(tq, m * fanout))
    alive = (in_range
             & (blen[coord[..., None], child] > 0)
             & jnp.isfinite(us)[..., None])                 # [tq, M, f]
    rb_ref[...] = jnp.where(alive.reshape(tq, m * fanout), rb, NEG)
    flat_ref[...] = (li[..., None] * nb
                     + child).reshape(tq, m * fanout).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("m", "fanout", "tile_q",
                                             "interpret"))
def router_hier_pallas(lists: jax.Array, q_dense: jax.Array,
                       sup_coords: jax.Array, sup_q: jax.Array,
                       sup_scale: jax.Array, sup_zero: jax.Array,
                       sum_coords: jax.Array, sum_q: jax.Array,
                       sum_scale: jax.Array, sum_zero: jax.Array,
                       block_len: jax.Array, *, m: int, fanout: int,
                       tile_q: int = 8, interpret: bool = True
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused two-stage route: stage A over the superblock tier, top-``m``
    per query, in-VMEM child-summary gather, stage B — one launch.

    Returns (rb [Q, m*fanout] child scores with pruned/dead at -inf,
    flat [Q, m*fanout] positions into the [cut*nb] routed layout); the
    host scatters them (output-sized work, no summary-sized
    intermediate). Q must be a multiple of tile_q (ops.py pads).
    """
    qn, cut = lists.shape
    l, ns, s2 = sup_coords.shape
    _, nb, s = sum_coords.shape
    d = q_dense.shape[1]
    assert q_dense.shape[0] == qn and qn % tile_q == 0, (
        q_dense.shape, lists.shape, tile_q)
    assert 0 < m <= cut * ns, (m, cut, ns)
    grid = (qn // tile_q,)
    sup3 = pl.BlockSpec((l, ns, s2), lambda i: (0, 0, 0))
    sup2 = pl.BlockSpec((l, ns), lambda i: (0, 0))
    sum3 = pl.BlockSpec((l, nb, s), lambda i: (0, 0, 0))
    sum2 = pl.BlockSpec((l, nb), lambda i: (0, 0))
    out_spec = pl.BlockSpec((tile_q, m * fanout), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_router_hier_kernel, m=m, fanout=fanout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, cut), lambda i: (i, 0)),
            pl.BlockSpec((tile_q, d), lambda i: (i, 0)),
            sup3, sup3, sup2, sup2,
            sum3, sum3, sum2, sum2, sum2,
        ],
        out_specs=(out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((qn, m * fanout), q_dense.dtype),
            jax.ShapeDtypeStruct((qn, m * fanout), jnp.int32),
        ),
        name="router_hier",
        interpret=interpret,
    )(lists, q_dense, sup_coords, sup_q, sup_scale, sup_zero,
      sum_coords, sum_q, sum_scale, sum_zero, block_len)
