"""Pallas TPU kernel: natively query-batched sparse·dense inner
products (Seismic phase S, Alg. 2 line 9).

For a whole query batch and its per-query candidate tiles in padded-CSR
layout, computes

    scores[q, n] = sum_j q_dense[q, coords[q, n, j]] * vals[q, n, j]

in ONE kernel launch. This is the op the paper engineers around x86
cache misses with prefetch intrinsics (§5.4); the TPU analog streams
candidate tiles HBM->VMEM while the dense query tile stays
VMEM-resident across the inner grid axis.

When the forward index is compact (u8 values, ``fwd_quant=True``) the
per-doc affine dequantization ((level-1)*scale + zero, level 0 = pad)
fuses into the multiply — candidate values cross HBM as one byte each
and are never materialized as floats.

Tiling (ops.py pads Q to tile_q and N to tile_n — the row width nnz
passes through as the full last dim):
  grid = (Q / tile_q, N / tile_n)   — queries x candidate tiles
  q pairs       [tile_q, nq]        query (coord, value) pairs in SMEM
  coords/vals   [tile_q, tile_n, nnz]
  scale/zero    [tile_q, tile_n]    (quantized variant only)
  out           [tile_q, tile_n]

Each query row of the tile is scored on its own [tile_n, nnz] slab,
with the query weight of every candidate coordinate matched from the
row's pairs (:mod:`repro.kernels.sparse_query`) — the form Mosaic
lowers for v5e. Interpret mode (auto-selected off-TPU by ops.py) runs
the same program on CPU for the ref.py parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_query import match_gather, pair_spec


def _gather_dot_kernel(qc_ref, qv_ref, coords_ref, vals_ref, out_ref):
    for i in range(coords_ref.shape[0]):        # query rows of the tile
        g = match_gather(qc_ref, qv_ref, i, coords_ref[i])  # [tn, nnz]
        out_ref[i] = (g * vals_ref[i].astype(jnp.float32)).sum(axis=-1)


def _gather_dot_quant_kernel(qc_ref, qv_ref, coords_ref, vals_ref,
                             scale_ref, zero_ref, out_ref):
    for i in range(coords_ref.shape[0]):        # query rows of the tile
        g = match_gather(qc_ref, qv_ref, i, coords_ref[i])  # [tn, nnz]
        u8 = vals_ref[i].astype(jnp.int32).astype(jnp.float32)
        deq = (u8 - 1.0) * scale_ref[i][:, None] + zero_ref[i][:, None]
        deq = jnp.where(u8 > 0, deq, 0.0)       # level 0 == padding
        out_ref[i] = (g * deq).sum(axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("tile_q", "tile_n", "interpret"))
def gather_dot_batch_pallas(q_coords: jax.Array, q_vals: jax.Array,
                            coords: jax.Array, vals: jax.Array,
                            scale: jax.Array | None = None,
                            zero: jax.Array | None = None, *,
                            tile_q: int = 8, tile_n: int = 128,
                            interpret: bool = True) -> jax.Array:
    """scores [Q, N] f32 = sum_j q[q, coords[q, :, j]] * vals[q, :, j],
    with the query given as (i32 coords, f32 vals) pairs [Q, nq].

    Q must be a multiple of tile_q and N of tile_n (ops.py pads). With
    (scale, zero) given, vals is u8 and dequant fuses into the dot.
    """
    qn, n, nnz = coords.shape
    nq = q_coords.shape[1]
    assert q_coords.shape[0] == qn and qn % tile_q == 0 and n % tile_n == 0, (
        q_coords.shape, coords.shape, tile_q, tile_n)
    grid = (qn // tile_q, n // tile_n)
    q_spec = pair_spec(tile_q, nq)
    row_spec = pl.BlockSpec((tile_q, tile_n, nnz), lambda i, j: (i, j, 0))
    sz_spec = pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j))
    quant = scale is not None
    kernel = _gather_dot_quant_kernel if quant else _gather_dot_kernel
    in_specs = [q_spec, q_spec, row_spec, row_spec] \
        + ([sz_spec, sz_spec] if quant else [])
    args = (q_coords, q_vals, coords, vals) + ((scale, zero) if quant else ())
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=sz_spec,
        out_shape=jax.ShapeDtypeStruct((qn, n), jnp.float32),
        interpret=interpret,
    )(*args)


def gather_dot_pallas(q_dense: jax.Array, coords: jax.Array,
                      vals: jax.Array, *, tile_n: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """Single-query compatibility shim: scores [N] via the batched
    kernel with Q=1 (kept for callers/tests of the pre-batch API).
    N must be a multiple of tile_n (ops.py pads)."""
    from repro.kernels.gather_dot.ops import _pad_batch_call
    return _pad_batch_call(q_dense[None], coords[None], vals[None],
                           None, None, tile_n=tile_n, interpret=interpret)[0]


# --------------------------------------------------------------------------
# Candidate-driven variant: the kernel receives candidate DOC IDS and the
# whole forward plane, gathers each candidate's (coords, vals) row itself,
# and skips tiles that are 100% sentinel. This is the compaction partner
# (SearchParams.fuse_level >= 1): the scorer packs live candidates to a
# prefix, so at high dedupe rates most candidate tiles are pure sentinel
# and the kernel's pl.when predicate skips their gather + dot entirely —
# tile_n work shrinks with the dedupe rate instead of being paid on every
# padded slot. Host-side nothing [Q, C, nnz]-shaped is ever materialized.
#
# Coverage boundary: the forward-plane operands ride in whole-array
# blocks, which interpret mode (CPU CI) executes exactly; the Mosaic
# lowering needs them VMEM-resident or an ANY-space DMA variant — see
# src/repro/kernels/README.md ("interpret vs Mosaic").
# --------------------------------------------------------------------------


def _cand_scores(q, cand, fwd_coords, fwd_vals, scale, zero, n_docs):
    """Shared scoring body: gather candidate rows, (dequant,) dot, mask
    sentinels to -inf. Bit-identical math to the host-gather path."""
    c = jnp.take(fwd_coords, cand, axis=0, mode="clip").astype(jnp.int32)
    v = jnp.take(fwd_vals, cand, axis=0, mode="clip")
    tq, tn, nnz = c.shape
    gathered = jnp.take_along_axis(
        q, c.reshape(tq, tn * nnz), axis=1).reshape(tq, tn, nnz)
    if scale is not None:
        u8 = v.astype(q.dtype)
        s = jnp.take(scale, cand, mode="clip").astype(q.dtype)
        z = jnp.take(zero, cand, mode="clip").astype(q.dtype)
        deq = (u8 - 1.0) * s[..., None] + z[..., None]
        v = jnp.where(u8 > 0, deq, 0.0)     # level 0 == padding
    else:
        v = v.astype(q.dtype)
    out = (gathered * v).sum(axis=-1)
    return jnp.where(cand < n_docs, out, -jnp.inf)


def _gather_dot_cand_kernel(cand_ref, q_ref, fwdc_ref, fwdv_ref, out_ref,
                            *, n_docs):
    cand = cand_ref[...]                        # [tq, tn]
    out_ref[...] = jnp.full(cand.shape, -jnp.inf, out_ref.dtype)

    @pl.when(jnp.any(cand < n_docs))            # all-sentinel tile: skip
    def _process():
        out_ref[...] = _cand_scores(q_ref[...], cand, fwdc_ref[...],
                                    fwdv_ref[...], None, None, n_docs)


def _gather_dot_cand_quant_kernel(cand_ref, q_ref, fwdc_ref, fwdv_ref,
                                  fs_ref, fz_ref, out_ref, *, n_docs):
    cand = cand_ref[...]                        # [tq, tn]
    out_ref[...] = jnp.full(cand.shape, -jnp.inf, out_ref.dtype)

    @pl.when(jnp.any(cand < n_docs))            # all-sentinel tile: skip
    def _process():
        out_ref[...] = _cand_scores(q_ref[...], cand, fwdc_ref[...],
                                    fwdv_ref[...], fs_ref[...], fz_ref[...],
                                    n_docs)


@functools.partial(jax.jit, static_argnames=("n_docs", "tile_q", "tile_n",
                                             "interpret"))
def gather_dot_cand_pallas(q_dense: jax.Array, cand: jax.Array,
                           fwd_coords: jax.Array, fwd_vals: jax.Array,
                           fwd_scale: jax.Array | None = None,
                           fwd_zero: jax.Array | None = None, *,
                           n_docs: int, tile_q: int = 8, tile_n: int = 128,
                           interpret: bool = True) -> jax.Array:
    """scores [Q, C] for candidate doc ids [Q, C] against the forward
    plane [N, nnz]; sentinel ids (>= n_docs) score -inf, all-sentinel
    tiles are skipped. Q % tile_q == 0 and C % tile_n == 0 (ops.py pads
    with the sentinel, so padding lands in skipped tiles).
    """
    qn, c = cand.shape
    assert q_dense.shape[0] == qn and qn % tile_q == 0 and c % tile_n == 0, (
        q_dense.shape, cand.shape, tile_q, tile_n)
    grid = (qn // tile_q, c // tile_n)
    d = q_dense.shape[1]
    n, nnz = fwd_coords.shape
    tile_spec = pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j))
    q_spec = pl.BlockSpec((tile_q, d), lambda i, j: (i, 0))
    plane_spec = pl.BlockSpec((n, nnz), lambda i, j: (0, 0))
    doc_spec = pl.BlockSpec((n,), lambda i, j: (0,))
    quant = fwd_scale is not None
    kernel = (_gather_dot_cand_quant_kernel if quant
              else _gather_dot_cand_kernel)
    in_specs = [tile_spec, q_spec, plane_spec, plane_spec] \
        + ([doc_spec, doc_spec] if quant else [])
    args = (cand, q_dense, fwd_coords, fwd_vals) \
        + ((fwd_scale, fwd_zero) if quant else ())
    return pl.pallas_call(
        functools.partial(kernel, n_docs=n_docs),
        grid=grid,
        in_specs=in_specs,
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((qn, c), q_dense.dtype),
        interpret=interpret,
    )(*args)
