"""Padded-sparse vector substrate.

Learned sparse embeddings (SPLADE-family) are nonnegative vectors in
R^d with ~40-200 non-zeros out of d~30k. TPUs want fixed shapes, so the
canonical representation here is *padded CSR rows*:

    coords: int32 [N, nnz_max]   (padding entries point at coord 0)
    vals:   float [N, nnz_max]   (padding entries are exactly 0.0)

A padded entry contributes 0 to every inner product, so no masks are
needed on the scoring path; masks are recovered as ``vals > 0`` when
structure matters (counts, summaries).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedSparse:
    """A batch of sparse vectors in padded CSR-row layout."""

    coords: jax.Array  # int32 [N, nnz_max]
    vals: jax.Array    # float [N, nnz_max], padding == 0.0
    dim: int = dataclasses.field(metadata=dict(static=True), default=0)

    @property
    def n(self) -> int:
        return self.coords.shape[0]

    @property
    def nnz_max(self) -> int:
        return self.coords.shape[1]

    def nnz(self) -> jax.Array:
        return (self.vals != 0).sum(axis=-1)

    def astype(self, dtype) -> "PaddedSparse":
        return PaddedSparse(self.coords, self.vals.astype(dtype), self.dim)

    def __getitem__(self, idx) -> "PaddedSparse":
        return PaddedSparse(self.coords[idx], self.vals[idx], self.dim)


def densify(ps: PaddedSparse, dtype=jnp.float32) -> jax.Array:
    """[N, nnz] padded-sparse -> [N, d] dense. Padding adds 0 at coord 0."""
    n = ps.coords.shape[0]
    out = jnp.zeros((n, ps.dim), dtype=dtype)
    rows = jnp.arange(n)[:, None]
    return out.at[rows, ps.coords].add(ps.vals.astype(dtype))


def densify_one(coords: jax.Array, vals: jax.Array, dim: int,
                dtype=jnp.float32) -> jax.Array:
    """[nnz] sparse -> [d] dense."""
    return jnp.zeros((dim,), dtype=dtype).at[coords].add(vals.astype(dtype))


def sparsify(dense: jax.Array, nnz_max: int) -> PaddedSparse:
    """[N, d] dense -> padded-sparse keeping the nnz_max largest entries.

    Exact when each row has <= nnz_max non-zeros (padding keeps val 0).
    """
    vals, coords = jax.lax.top_k(dense, nnz_max)
    vals = jnp.where(vals > 0, vals, 0.0)
    coords = jnp.where(vals > 0, coords, 0)
    return PaddedSparse(coords.astype(jnp.int32), vals, dense.shape[-1])


def inner_product_padded(q_dense: jax.Array, coords: jax.Array,
                         vals: jax.Array) -> jax.Array:
    """<q, x> for dense q [d] against padded-sparse rows [N, nnz] -> [N].

    The jnp reference for the ``gather_dot`` Pallas kernel.
    """
    return (q_dense[coords] * vals).sum(axis=-1)


@partial(jax.jit, static_argnames=("out_nnz",))
def alpha_mass_subvector(coords: jax.Array, vals: jax.Array, alpha: float,
                         out_nnz: int) -> tuple[jax.Array, jax.Array]:
    """Definition 3.1: keep the largest-|value| entries while their
    cumulative L1 mass stays within ``alpha * ||x||_1``; at least one
    entry is always kept. Output is padded to ``out_nnz`` entries.

    Only the ``out_nnz`` largest entries can be kept, so a top-k (ties
    to the lower index, as a stable sort) replaces sorting the whole
    vector — on a TPU a d=30522 summary row sorts ~20x slower than its
    top-96 selects.
    """
    mag, order = jax.lax.top_k(jnp.abs(vals), min(out_nnz, vals.shape[0]))
    sv = vals[order]
    sc = coords[order]
    cum = jnp.cumsum(mag)
    # the total can't round below the kept prefix (alpha=1 keeps all)
    keep = cum <= alpha * jnp.maximum(jnp.abs(vals).sum(), cum[-1])
    keep = keep.at[0].set(True)  # never emit an empty subvector
    sv = jnp.where(keep, sv, 0.0)
    sc = jnp.where(keep, sc, 0)
    pad = out_nnz - sv.shape[0]
    if pad > 0:
        sv = jnp.pad(sv, (0, pad))
        sc = jnp.pad(sc, (0, pad))
    return sc.astype(jnp.int32), sv


def top_cut(coords: jax.Array, vals: jax.Array, cut: int) -> tuple[jax.Array, jax.Array]:
    """The ``cut`` largest-value entries of one sparse vector (Alg. 2, L1)."""
    v, idx = jax.lax.top_k(vals, cut)
    c = jnp.take(coords, idx)
    c = jnp.where(v > 0, c, 0)
    v = jnp.where(v > 0, v, 0.0)
    return c.astype(jnp.int32), v


def l1_mass_fraction(vals: np.ndarray, top: int) -> np.ndarray:
    """Fraction of L1 mass captured by the ``top`` largest entries
    (numpy; used by the Fig. 1 concentration benchmark)."""
    v = np.sort(np.abs(vals), axis=-1)[..., ::-1]
    total = v.sum(axis=-1)
    total = np.where(total == 0, 1.0, total)
    return v[..., :top].sum(axis=-1) / total
