"""The plain reference: exact sparse maximum inner product search.

It imports nothing of the program. The corpus is taken as the
configuration stores it (``stored``: each document value rounded to
the forward plane's dtype, then held in f32), and every score is an
f32 (on the chip) or f64 (on the host) sum of query value times stored
document value.

* :func:`exact_topk` — the exact top-k of every query over the whole
  corpus, on the device, scanning document chunks with a running top-k
  so that nothing ``[Q, N, nnz]``-shaped exists. Ties keep the lower
  document id.
* :func:`exact_topk_ranged` — the same answer for a corpus spread over
  several devices: one contiguous range of documents scanned on each,
  merged on the host.
* :func:`pair_scores` — the exact score of given (query, document)
  pairs, on the host in f64.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

CHUNK_BYTES = 1 << 29          # one scan step's [chunk, nnz, Q] f32 gather


def stored(vals: np.ndarray, dtype: str) -> np.ndarray:
    """Document values as a forward plane of ``dtype`` holds them."""
    if dtype == "float32":
        return np.asarray(vals, np.float32)
    dt = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}[dtype]
    return np.asarray(vals, np.float32).astype(dt).astype(np.float32)


def dense_queries(q_coords: np.ndarray, q_vals: np.ndarray,
                  dim: int) -> np.ndarray:
    """[Q, dim] f32; padding slots carry value 0."""
    out = np.zeros((q_coords.shape[0], dim), np.float32)
    rows = np.arange(q_coords.shape[0])[:, None]
    np.add.at(out, (rows, q_coords), np.where(q_vals > 0, q_vals, 0))
    return out


def pair_scores(q_dense: np.ndarray, doc_coords: np.ndarray,
                doc_vals: np.ndarray, q: np.ndarray,
                d: np.ndarray) -> np.ndarray:
    """Exact <query q[i], doc d[i]> in f64 for each pair i."""
    c = doc_coords[d]                                    # [P, nnz]
    v = doc_vals[d].astype(np.float64)
    return (q_dense[q[:, None], c].astype(np.float64) * v).sum(axis=1)


def exact_topk(doc_coords: np.ndarray, doc_vals: np.ndarray,
               q_dense: np.ndarray, k: int, device=None):
    """(scores [Q, k] f32, ids [Q, k] int32) of the exact search over
    every document, computed on ``device``."""
    s, i = _scan_on(doc_coords, doc_vals, q_dense, k, device)
    return np.asarray(s), np.asarray(i)


def exact_topk_ranged(doc_coords: np.ndarray, doc_vals: np.ndarray,
                      q_dense: np.ndarray, k: int, devices):
    """:func:`exact_topk` of a corpus too large for one device: range
    ``r`` of ``len(devices)`` contiguous ranges of documents (``[r * n
    // R, (r + 1) * n // R)``) is scanned on ``devices[r]``, each range
    padded on its own, all ranges at once; the ranges' top-k are merged
    on the host, ties to the lower id, in the corpus's own numbering."""
    n = doc_coords.shape[0]
    r = len(devices)
    bounds = [i * n // r for i in range(r + 1)]
    parts = [(lo, _scan_on(doc_coords[lo:hi], doc_vals[lo:hi], q_dense, k,
                           dev))
             for lo, hi, dev in zip(bounds, bounds[1:], devices)]
    scores = np.concatenate([np.asarray(s) for _, (s, _) in parts], axis=1)
    ids = np.concatenate([np.asarray(i) + lo for lo, (_, i) in parts],
                         axis=1)
    order = np.lexsort((ids, -scores), axis=1)[:, :k]
    return (np.take_along_axis(scores, order, axis=1),
            np.take_along_axis(ids, order, axis=1).astype(np.int32))


def _scan_on(doc_coords, doc_vals, q_dense, k: int, device):
    """Dispatch the chunked scan of ``doc_coords`` on ``device``; the
    device's (scores, ids), not waited for."""
    n, nnz = doc_coords.shape
    qn = q_dense.shape[0]
    chunk = int(max(8, min(n, CHUNK_BYTES // (nnz * qn * 4))))
    steps = -(-n // chunk)
    pad = steps * chunk - n
    coords = np.pad(doc_coords, ((0, pad), (0, 0))).reshape(steps, chunk,
                                                            nnz)
    vals = np.pad(doc_vals.astype(np.float32),
                  ((0, pad), (0, 0))).reshape(steps, chunk, nnz)
    put = partial(jax.device_put, device=device)
    return _scan_topk(put(coords), put(vals), put(q_dense.T.copy()),
                      n=n, k=k)


@partial(jax.jit, static_argnames=("n", "k"))
def _scan_topk(coords, vals, q_t, *, n: int, k: int):
    steps, chunk, _ = coords.shape
    qn = q_t.shape[1]

    def step(carry, xs):
        best_s, best_i = carry
        j, c, v = xs
        s = jnp.einsum("cnq,cn->qc", q_t[c], v,
                       precision=jax.lax.Precision.HIGHEST)
        ids = j * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(ids[None, :] < n, s, -jnp.inf)
        all_s = jnp.concatenate([best_s, s], axis=1)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (qn, chunk))], axis=1)
        top_s, pos = jax.lax.top_k(all_s, k)
        return (top_s, jnp.take_along_axis(all_i, pos, axis=1)), None

    init = (jnp.full((qn, k), -jnp.inf, jnp.float32),
            jnp.full((qn, k), -1, jnp.int32))
    (s, i), _ = jax.lax.scan(
        step, init, (jnp.arange(steps, dtype=jnp.int32), coords, vals))
    return s, i

