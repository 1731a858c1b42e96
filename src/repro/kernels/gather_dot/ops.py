"""Public wrappers for gather_dot: pad to tile multiples, pick tiles
from the shared VMEM model, resolve interpret mode centrally.

``gather_dot_batch``       [Q, N, nnz] pre-gathered candidate rows ->
                           [Q, N] exact scores; optional fused u8
                           dequant via (scale, zero)
``gather_dot_cand_batch``  [Q, C] candidate DOC IDS + the forward plane
                           -> [Q, C] scores; the gather happens inside
                           the kernel and all-sentinel tiles are
                           skipped (the compaction fast path,
                           ``SearchParams.fuse_level >= 1``)
``gather_dot``             single-query [N, nnz] compatibility API

All wrappers resolve interpret mode through the single
:func:`repro.kernels.runtime.default_interpret` helper (auto-select
off-TPU; explicit bool overrides) — no wrapper hardcodes its own
default anymore. Tiling comes from :mod:`repro.kernels.tiling` unless
pinned explicitly (the microbench sweep pins it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gather_dot.gather_dot import (gather_dot_batch_pallas,
                                                 gather_dot_cand_pallas,
                                                 gather_dot_pallas)
from repro.kernels.gather_dot.ref import gather_dot_batch_ref, gather_dot_ref
from repro.kernels.runtime import default_interpret
from repro.kernels.sparse_query import pad_pairs, query_pairs
from repro.kernels.tiling import TileChoice, choose_tiles, gather_row_bytes

_TILE_Q = 8     # minimum aligned tile (f32 sublane) — chooser floor
_TILE_N = 128   # minimum aligned tile (lane width) — chooser floor


def cand_tile_choice(qn: int, c: int, nnz: int, *, quant: bool,
                     dim: int) -> TileChoice:
    """THE tile choice of the candidate-driven kernel for a [qn, c]
    launch — one definition shared by ``gather_dot_cand_batch`` and
    the microbench/throughput reports, so every
    ``cand_tiles_processed`` mirror evaluates the kernel's
    actual tiling (the +4 charges the in-kernel candidate-id column)."""
    return choose_tiles(qn, c,
                        row_bytes=gather_row_bytes(nnz, quant=quant) + 4,
                        q_row_bytes=4 * dim)


def _pad_batch_call(q, coords, vals, scale, zero, *,
                    tile_q=None, tile_n=None, interpret=None):
    """Choose tiles, pad Q/N up to them, launch, slice back."""
    interpret = default_interpret(interpret)
    qc, qv, out_dtype = query_pairs(q)
    qn, n, nnz = coords.shape
    if tile_q is None or tile_n is None:
        ch = choose_tiles(qn, n,
                          row_bytes=gather_row_bytes(
                              nnz, quant=scale is not None),
                          q_row_bytes=0)      # query pairs live in SMEM
        tile_q = tile_q if tile_q is not None else ch.tile_q
        tile_n = tile_n if tile_n is not None else ch.tile_n
    pq = (-qn) % tile_q
    pn = (-n) % tile_n
    qc, qv = pad_pairs(qc, qv, pq)
    if pq or pn:
        coords = jnp.pad(coords, ((0, pq), (0, pn), (0, 0)))
        vals = jnp.pad(vals, ((0, pq), (0, pn), (0, 0)))
        if scale is not None:
            scale = jnp.pad(scale, ((0, pq), (0, pn)))
            zero = jnp.pad(zero, ((0, pq), (0, pn)))
    out = gather_dot_batch_pallas(qc, qv, coords, vals, scale, zero,
                                  tile_q=tile_q, tile_n=tile_n,
                                  interpret=interpret)
    return out[:qn, :n].astype(out_dtype)


def gather_dot_batch(q, coords: jax.Array,
                     vals: jax.Array, scale: jax.Array | None = None,
                     zero: jax.Array | None = None, *,
                     tile_q: int | None = None, tile_n: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Batched sparse·dense scoring [Q, N, nnz] -> [Q, N]; ``q`` is a
    ``PaddedSparse`` query batch or a dense ``[Q, d]`` one.

    With (scale, zero) given, ``vals`` is uint8 and the per-doc affine
    dequantization fuses into the kernel (compact forward index)."""
    return _pad_batch_call(q, coords, vals, scale, zero,
                           tile_q=tile_q, tile_n=tile_n, interpret=interpret)


def gather_dot_cand_batch(q_dense: jax.Array, cand: jax.Array,
                          fwd_coords: jax.Array, fwd_vals: jax.Array,
                          fwd_scale: jax.Array | None = None,
                          fwd_zero: jax.Array | None = None, *,
                          n_docs: int, tile_q: int | None = None,
                          tile_n: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """Candidate-driven scoring: ids [Q, C] + forward plane [N, nnz] ->
    scores [Q, C] (sentinel ids >= n_docs -> -inf).

    The forward gather runs inside the kernel (no [Q, C, nnz] HBM
    intermediate) and tiles whose candidates are all sentinel are
    skipped — pack live candidates to a prefix first
    (``scorer.compact_candidates``) to maximize skipped tiles.
    Q/C padding uses the sentinel, so padding lands in skipped tiles.
    """
    interpret = default_interpret(interpret)
    qn, c = cand.shape
    nnz = fwd_coords.shape[1]
    if tile_q is None or tile_n is None:
        ch = cand_tile_choice(qn, c, nnz,
                              quant=fwd_scale is not None,
                              dim=q_dense.shape[1])
        tile_q = tile_q if tile_q is not None else ch.tile_q
        tile_n = tile_n if tile_n is not None else ch.tile_n
    pq = (-qn) % tile_q
    pn = (-c) % tile_n
    if pq or pn:
        q_dense = jnp.pad(q_dense, ((0, pq), (0, 0)))
        cand = jnp.pad(cand, ((0, pq), (0, pn)),
                       constant_values=n_docs)    # padding == sentinel
    out = gather_dot_cand_pallas(q_dense, cand, fwd_coords, fwd_vals,
                                 fwd_scale, fwd_zero, n_docs=n_docs,
                                 tile_q=tile_q, tile_n=tile_n,
                                 interpret=interpret)
    return out[:qn, :c]


def cand_tiles_processed(cand, n_docs: int, tile_q: int,
                         tile_n: int) -> np.ndarray:
    """Host-side mirror of the candidate kernel's skip predicate:
    bool [gridQ, gridN] — True where a tile holds at least one live
    candidate and the kernel runs its gather + dot.

    This IS the work model the microbench and the compaction smoke
    gate report (``scored slots = processed.sum() * tile_q * tile_n``);
    it matches the kernel's ``pl.when`` decision bit-for-bit because it
    evaluates the same predicate on the same padded layout.
    """
    a = np.asarray(cand)
    qn, c = a.shape
    pq = (-qn) % tile_q
    pn = (-c) % tile_n
    if pq or pn:
        a = np.pad(a, ((0, pq), (0, pn)), constant_values=n_docs)
    gq, gn = a.shape[0] // tile_q, a.shape[1] // tile_n
    live = (a < n_docs).reshape(gq, tile_q, gn, tile_n)
    return live.any(axis=(1, 3))


def gather_dot(q_dense: jax.Array, coords: jax.Array,
               vals: jax.Array, *,
               interpret: bool | None = None) -> jax.Array:
    """Single-query sparse·dense scoring [N, nnz] -> [N] (pre-batch
    compatibility API)."""
    return gather_dot_pallas(q_dense, coords, vals, interpret=interpret)


__all__ = ["gather_dot", "gather_dot_batch", "gather_dot_cand_batch",
           "cand_tile_choice", "cand_tiles_processed", "gather_dot_ref",
           "gather_dot_batch_ref"]
