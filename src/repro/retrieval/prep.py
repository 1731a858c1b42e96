"""Stage 1 — prep: batch query densification + probed-coordinate cut.

Input is the padded-CSR query batch; output is the dense query matrix
(kept VMEM-resident by the downstream kernels) and the top-``cut``
coordinates each query probes (Alg. 2 line 1), computed for the whole
batch with one top_k.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.sparse.ops import PaddedSparse, densify


def prep_queries(q_coords: jax.Array, q_vals: jax.Array, dim: int,
                 cut: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """[Q, nnz] padded-sparse queries -> (q_dense [Q, d],
    lists [Q, cut] int32, list_vals [Q, cut]).

    Padded entries (val == 0) map to coord 0 with val 0; probing coord 0
    repeatedly is harmless — its routed blocks dedupe downstream.
    """
    vals = q_vals.astype(jnp.float32)
    q_dense = densify(PaddedSparse(q_coords, vals, dim))
    cv, idx = jax.lax.top_k(vals, cut)                      # [Q, cut]
    cc = jnp.take_along_axis(q_coords, idx, axis=1)
    cc = jnp.where(cv > 0, cc, 0)
    cv = jnp.where(cv > 0, cv, 0.0)
    return q_dense, cc.astype(jnp.int32), cv


def probed_lists(q_coords, q_vals, dim: int, cut: int):
    """The distinct coordinates whose inverted lists a query batch
    reads (host numpy array) — exactly the lists ``prep_queries``
    probes."""
    _, lists, _ = prep_queries(jnp.asarray(q_coords), jnp.asarray(q_vals),
                               dim, cut)
    return np.unique(np.asarray(lists))
