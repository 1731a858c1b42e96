"""Public wrappers for summary_dot: pad to tile multiples, pick tiles
from the shared VMEM model, resolve interpret mode centrally.

``summary_dot_batch``  [Q, L, S] summaries -> [Q, L] routing scores
                       (one kernel launch for the whole query batch;
                       the query batch is padded-sparse or dense, see
                       :func:`repro.kernels.sparse_query.query_pairs`)
``summary_dot``        single-query [cut, nb, S] compatibility API

Tiling is chosen per launch shape by :mod:`repro.kernels.tiling`
(lane/sublane-aligned, VMEM-budgeted, never wider than the padded
problem); pass explicit ``tile_q`` / ``tile_l`` to pin a tiling (the
microbench sweep does). Results are tile-invariant — every output
element is an independent sum — which the parity tests pin.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.runtime import default_interpret
from repro.kernels.sparse_query import pad_pairs, query_pairs
from repro.kernels.summary_dot.ref import (summary_dot_batch_ref,
                                           summary_dot_ref)
from repro.kernels.summary_dot.summary_dot import (summary_dot_batch_pallas,
                                                   summary_dot_pallas)
from repro.kernels.tiling import choose_tiles, summary_row_bytes

_TILE_Q = 8     # minimum aligned tile (f32 sublane) — chooser floor
_TILE_L = 128   # minimum aligned tile (lane width) — chooser floor


def _pad_batch_call(q, sum_coords, sum_q, sum_scale, sum_zero, *,
                    tile_q=None, tile_l=None, interpret=None):
    """Choose tiles, pad Q/L up to them, launch, slice back."""
    interpret = default_interpret(interpret)
    qc, qv, out_dtype = query_pairs(q)
    qn, l, s = sum_coords.shape
    if tile_q is None or tile_l is None:
        ch = choose_tiles(qn, l, row_bytes=summary_row_bytes(s),
                          q_row_bytes=0)      # query pairs live in SMEM
        tile_q = tile_q if tile_q is not None else ch.tile_q
        tile_l = tile_l if tile_l is not None else ch.tile_n
    pq = (-qn) % tile_q
    pls = (-l) % tile_l
    qc, qv = pad_pairs(qc, qv, pq)
    if pq or pls:
        sum_coords = jnp.pad(sum_coords, ((0, pq), (0, pls), (0, 0)))
        sum_q = jnp.pad(sum_q, ((0, pq), (0, pls), (0, 0)))
        sum_scale = jnp.pad(sum_scale, ((0, pq), (0, pls)))
        sum_zero = jnp.pad(sum_zero, ((0, pq), (0, pls)))
    out = summary_dot_batch_pallas(qc, qv, sum_coords, sum_q, sum_scale,
                                   sum_zero, tile_q=tile_q, tile_l=tile_l,
                                   interpret=interpret)
    return out[:qn, :l].astype(out_dtype)


def summary_dot_batch(q, sum_coords: jax.Array,
                      sum_q: jax.Array, sum_scale: jax.Array,
                      sum_zero: jax.Array, *, tile_q: int | None = None,
                      tile_l: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Batched quantized routing scores [Q, L]; dequant fused in-kernel.
    ``q`` is a ``PaddedSparse`` query batch or a dense ``[Q, d]`` one."""
    return _pad_batch_call(q, sum_coords, sum_q, sum_scale, sum_zero,
                           tile_q=tile_q, tile_l=tile_l, interpret=interpret)


def summary_dot(q_dense: jax.Array, sum_coords: jax.Array, sum_q: jax.Array,
                sum_scale: jax.Array, sum_zero: jax.Array, *,
                interpret: bool | None = None) -> jax.Array:
    """Single-query routing scores [cut, nb] (pre-batch compatibility)."""
    return summary_dot_pallas(q_dense, sum_coords, sum_q, sum_scale,
                              sum_zero, interpret=interpret)


__all__ = ["summary_dot", "summary_dot_batch", "summary_dot_ref",
           "summary_dot_batch_ref"]
