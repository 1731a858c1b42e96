"""The query operand of the unfused scoring kernels: (coord, value)
pairs instead of a dense vocabulary-wide row.

A row coordinate's query weight is found by matching it against the
query's pairs, which sit in SMEM and are read as scalars: every pair
adds its value where the coordinate equals its coord and zero
elsewhere. Query coordinates are distinct, so each coordinate matches
at most one pair and adding the zeros is exact — the result equals
``q_dense[coord]`` bit for bit. The cross-lane gather from a dense
``[d]`` row that this replaces has no Mosaic lowering.

``query_pairs`` accepts either form at the wrapper level: a padded-
sparse batch passes through (``query_nnz`` pairs per row, the served
path), a dense ``[Q, d]`` batch becomes ``d`` pairs per row (exact for
any query, but ``d`` match steps per row — a test and compatibility
path, not a serving one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sparse.ops import PaddedSparse


def query_pairs(q) -> tuple[jax.Array, jax.Array, jnp.dtype]:
    """Query batch -> (coords i32 [Q, nq], vals f32 [Q, nq], out dtype).

    ``q`` is a ``PaddedSparse`` batch or a dense ``[Q, d]`` array; the
    output dtype follows the query values."""
    if isinstance(q, PaddedSparse):
        return (q.coords.astype(jnp.int32), q.vals.astype(jnp.float32),
                jnp.dtype(jnp.float32))
    qn, d = q.shape
    coords = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (qn, d))
    return coords, q.astype(jnp.float32), q.dtype


def pair_spec(tile_q: int, nq: int) -> pl.BlockSpec:
    """SMEM block of one query tile's pairs (scalar reads in-kernel)."""
    return pl.BlockSpec((tile_q, nq), lambda i, j: (i, 0),
                        memory_space=pltpu.SMEM)


def match_gather(qc_ref, qv_ref, i: int, coords: jax.Array) -> jax.Array:
    """Query row ``i``'s weight at every entry of ``coords`` (any
    shape, int32), from its pairs in ``qc_ref``/``qv_ref``."""

    def body(j, g):
        return g + jnp.where(coords == qc_ref[i, j], qv_ref[i, j], 0.0)

    return jax.lax.fori_loop(0, qc_ref.shape[1], body,
                             jnp.zeros(coords.shape, jnp.float32))


def pad_pairs(qc: jax.Array, qv: jax.Array, pq: int):
    """Pad the query axis by ``pq`` all-zero rows."""
    if not pq:
        return qc, qv
    return (jnp.pad(qc, ((0, pq), (0, 0))), jnp.pad(qv, ((0, pq), (0, 0))))


__all__ = ["query_pairs", "pair_spec", "match_gather", "pad_pairs"]
