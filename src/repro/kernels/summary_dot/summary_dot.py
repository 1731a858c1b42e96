"""Pallas TPU kernel: natively query-batched quantized summary routing
(Seismic phase R).

For a whole query batch at once, computes

    r[q, l] = sum_s q_dense[q, sum_coords[q, l, s]] * dequant(sum_q[q, l, s])

where ``l`` runs over the flattened (probed list, block) axis and the
u8 affine dequantization ((level-1)*scale + zero, level 0 = padding)
is FUSED into the multiply — the paper's "matrix multiplication
against all quantized summaries of an inverted list" (§7.1), done for
the entire batch in ONE kernel launch and without ever materializing
the dequantized summaries in HBM.

Tiling (every block is >= 2-D; ops.py pads Q to tile_q and L to
tile_l — the summary width S passes through as the full last dim):

  grid = (Q / tile_q, L / tile_l)   — queries x summary tiles
  q pairs      [tile_q, nq]         query (coord, value) pairs in SMEM
  coords/sq    [tile_q, tile_l, S]  one summary tile per grid step
  scale/zero   [tile_q, tile_l]
  out          [tile_q, tile_l]

Each query row of the tile is scored on its own [tile_l, S] slab: the
query weight of every summary coordinate comes from matching it
against the row's pairs (:mod:`repro.kernels.sparse_query`), u8
levels widen through int32 to f32, and the slab reduces over S. This
is the form Mosaic lowers for v5e; interpret mode (selected
automatically off-TPU by ops.py) runs the same program on CPU and is
what the parity tests pin against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sparse_query import match_gather, pair_spec


def _summary_dot_kernel(qc_ref, qv_ref, coords_ref, sq_ref, scale_ref,
                        zero_ref, out_ref):
    for i in range(coords_ref.shape[0]):            # query rows of the tile
        g = match_gather(qc_ref, qv_ref, i, coords_ref[i])      # [tl, S]
        sq = sq_ref[i].astype(jnp.int32).astype(jnp.float32)
        deq = (sq - 1.0) * scale_ref[i][:, None] + zero_ref[i][:, None]
        deq = jnp.where(sq > 0, deq, 0.0)           # level 0 == padding
        out_ref[i] = (g * deq).sum(axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("tile_q", "tile_l", "interpret"))
def summary_dot_batch_pallas(q_coords: jax.Array, q_vals: jax.Array,
                             sum_coords: jax.Array, sum_q: jax.Array,
                             sum_scale: jax.Array, sum_zero: jax.Array, *,
                             tile_q: int = 8, tile_l: int = 128,
                             interpret: bool = True) -> jax.Array:
    """r [Q, L] f32 from quantized summaries [Q, L, S] and query pairs
    (i32 coords, f32 vals) [Q, nq]; one launch per batch.

    Q must be a multiple of tile_q and L of tile_l (ops.py pads).
    """
    qn, l, s = sum_coords.shape
    nq = q_coords.shape[1]
    assert q_coords.shape[0] == qn and qn % tile_q == 0 and l % tile_l == 0, (
        q_coords.shape, sum_coords.shape, tile_q, tile_l)
    grid = (qn // tile_q, l // tile_l)
    return pl.pallas_call(
        _summary_dot_kernel,
        grid=grid,
        in_specs=[
            pair_spec(tile_q, nq), pair_spec(tile_q, nq),
            pl.BlockSpec((tile_q, tile_l, s), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tile_q, tile_l, s), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tile_q, tile_l), lambda i, j: (i, j)),
            pl.BlockSpec((tile_q, tile_l), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_l), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, l), jnp.float32),
        interpret=interpret,
    )(q_coords, q_vals, sum_coords, sum_q, sum_scale, sum_zero)


def summary_dot_pallas(q_dense: jax.Array, sum_coords: jax.Array,
                       sum_q: jax.Array, sum_scale: jax.Array,
                       sum_zero: jax.Array, *,
                       interpret: bool | None = None) -> jax.Array:
    """Single-query compatibility shim: r [cut, nb] via the batched
    kernel with Q=1 (kept for callers/tests of the pre-batch API)."""
    from repro.kernels.summary_dot.ops import _pad_batch_call
    cut, nb, s = sum_coords.shape
    r = _pad_batch_call(q_dense[None], sum_coords.reshape(1, cut * nb, s),
                        sum_q.reshape(1, cut * nb, s),
                        sum_scale.reshape(1, cut * nb),
                        sum_zero.reshape(1, cut * nb), interpret=interpret)
    return r[0].reshape(cut, nb)
