"""Baseline retrieval systems the paper compares against (§7.1).

* ``exact_search``        — brute-force MIPS over the forward index
                            (PISA's role: the exact, rank-safe
                            reference; also the recall ground truth).
* ``IvfIndex``            — SparseIvf [Bruch et al. '23]: documents
                            clustered once globally; the query probes
                            the ``nprobe`` closest centroids and
                            exactly scores every doc in them.
* ``impact_search``       — IOQP-style impact-ordered evaluation: each
                            probed coordinate contributes its top
                            ``rho``-fraction of postings; partial
                            scores accumulate (score-at-a-time) and the
                            top-k of the accumulator is returned.

Graph baselines (GrassRMA / PyANN) are greedy best-first graph walks
whose per-hop data dependence does not map to a batched TPU execution
model; ``graph_baseline.IPNSWIndex`` implements them as a host-side
numpy oracle compared on the paper's own docs-evaluated axis (§7.2.1).
See DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.sparse.ops import PaddedSparse, densify, densify_one

NEG = -jnp.inf


# --------------------------------------------------------------------------
# Exact search (PISA reference point)
# --------------------------------------------------------------------------

ORACLE_CHUNK_BYTES = 1 << 29   # bound on one doc chunk's [c, nnz, Q] f32


def oracle_doc_chunk(n_docs: int, nnz: int, n_queries: int) -> int:
    """Docs per ``exact_search`` step: as many as keep one step's
    ``[chunk, nnz, Q]`` f32 gather under ``ORACLE_CHUNK_BYTES``."""
    per_doc = max(nnz * n_queries * 4, 1)
    return int(min(n_docs, max(8, ORACLE_CHUNK_BYTES // per_doc)))


@partial(jax.jit, static_argnames=("k", "doc_chunk"))
def exact_search(docs: PaddedSparse, queries: PaddedSparse, k: int,
                 doc_chunk: int | None = None):
    """Brute-force MIPS: every query scored against every doc via the
    padded gather-dot. Returns (scores [Q,k], ids [Q,k]).

    The corpus is scanned in chunks of ``doc_chunk`` docs (default
    :func:`oracle_doc_chunk`) with a running top-k, so nothing
    ``[Q, N, nnz]``-shaped is ever materialized (the whole-batch form
    needs ~33 GB at Q=64, N=1M, nnz=128). Each step gathers rows of the
    transposed dense queries ``[d, Q]`` — one row per doc coordinate,
    all queries at once. Score ties keep the lower doc id, as one
    top-k over the whole corpus would."""
    n, nnz = docs.coords.shape
    qn = queries.coords.shape[0]
    chunk = doc_chunk or oracle_doc_chunk(n, nnz, qn)
    steps = -(-n // chunk)
    pad = steps * chunk - n
    coords = jnp.pad(docs.coords, ((0, pad), (0, 0))).reshape(
        steps, chunk, nnz)
    vals = jnp.pad(docs.vals.astype(jnp.float32),
                   ((0, pad), (0, 0))).reshape(steps, chunk, nnz)
    q_t = densify(queries.astype(jnp.float32)).T               # [d, Q]
    kk = min(k, n)

    def step(carry, xs):
        best_s, best_i = carry
        i, c, v = xs
        s = jnp.einsum("cnq,cn->qc", q_t[c], v)                # [Q, chunk]
        ids = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(ids < n, s, NEG)
        all_s = jnp.concatenate([best_s, s], axis=1)
        all_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (qn, chunk))], axis=1)
        top_s, pos = jax.lax.top_k(all_s, kk)
        return (top_s, jnp.take_along_axis(all_i, pos, axis=1)), None

    init = (jnp.full((qn, kk), NEG, jnp.float32),
            jnp.full((qn, kk), n, jnp.int32))
    (top_s, top_i), _ = jax.lax.scan(
        step, init, (jnp.arange(steps, dtype=jnp.int32), coords, vals))
    return top_s, top_i


# --------------------------------------------------------------------------
# SparseIvf-style IVF
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IvfIndex:
    fwd: PaddedSparse
    centroids: jax.Array      # [C, d] dense f32
    member_docs: jax.Array    # int32 [C, cap] (N = pad)
    member_len: jax.Array     # int32 [C]
    cap: int = dataclasses.field(metadata=dict(static=True), default=0)


@partial(jax.jit, static_argnames=("n_clusters", "cap", "iters"))
def build_ivf(docs: PaddedSparse, n_clusters: int, cap: int,
              iters: int = 3, seed: int = 0) -> IvfIndex:
    """K-means (Lloyd, dense centroids) with max-IP assignment, matching
    the spherical-ish clustering SparseIvf uses; capacity-padded members."""
    n = docs.n
    dense = densify(docs)                                   # [N, d]
    key = jax.random.PRNGKey(seed)
    init = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = dense[init]

    def step(cent, _):
        ips = dense @ cent.T                                # [N, C]
        assign = jnp.argmax(ips, axis=-1)
        one_hot = jax.nn.one_hot(assign, n_clusters, dtype=jnp.float32)
        sums = one_hot.T @ dense
        cnt = one_hot.sum(0)[:, None]
        new = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1), cent)
        return new, assign

    cent, assigns = jax.lax.scan(step, cent, None, length=iters)
    assign = assigns[-1]
    # membership lists, capacity-capped
    order = jnp.argsort(assign, stable=True)
    sorted_assign = assign[order]
    start = jnp.searchsorted(sorted_assign, jnp.arange(n_clusters))
    ln = jnp.searchsorted(sorted_assign, jnp.arange(n_clusters) + 1) - start
    idx = start[:, None] + jnp.arange(cap)[None, :]
    member = jnp.where(jnp.arange(cap)[None, :] < jnp.minimum(ln, cap)[:, None],
                       jnp.take(order, jnp.clip(idx, 0, n - 1)), n)
    return IvfIndex(fwd=docs, centroids=cent, member_docs=member.astype(jnp.int32),
                    member_len=ln.astype(jnp.int32), cap=cap)


@partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_search(index: IvfIndex, queries: PaddedSparse, k: int, nprobe: int):
    """Probe the nprobe max-IP centroids, exactly score their members."""
    fwd = index.fwd

    def one(qc, qv):
        q = densify_one(qc, qv.astype(jnp.float32), fwd.dim)
        cs = index.centroids @ q                            # [C]
        _, probe = jax.lax.top_k(cs, nprobe)
        cand = index.member_docs[probe].reshape(-1)         # [nprobe*cap]
        c = jnp.take(fwd.coords, cand, axis=0, mode="clip")
        v = jnp.take(fwd.vals, cand, axis=0, mode="clip").astype(jnp.float32)
        s = (q[c] * v).sum(-1)
        s = jnp.where(cand < fwd.n, s, NEG)
        top_s, pos = jax.lax.top_k(s, k)
        ids = jnp.where(jnp.isfinite(top_s), cand[pos], -1)
        return top_s, ids.astype(jnp.int32), (cand < fwd.n).sum()

    return jax.vmap(one)(queries.coords, queries.vals)


# --------------------------------------------------------------------------
# IOQP-style impact-ordered, budgeted score-at-a-time
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "postings_per_list", "n_docs"))
def impact_search(list_docs: jax.Array, list_vals: jax.Array,
                  list_len: jax.Array, n_docs: int,
                  queries: PaddedSparse, k: int, postings_per_list: int):
    """Score-at-a-time over impact-ordered lists with a per-list budget
    (IOQP's `fraction` knob ~ postings_per_list / lam). Partial scores
    q_i * x_i accumulate in a dense [N] accumulator per query.

    Takes the *unblocked* impact-ordered lists from the Seismic index
    (list_docs/list_vals are already value-sorted per coordinate before
    permutation — we re-sort here to be explicit)."""
    lam = list_docs.shape[1]
    b = min(postings_per_list, lam)

    def one(qc, qv):
        acc = jnp.zeros((n_docs + 1,), jnp.float32)
        docs = list_docs[qc]                                # [nnz_q, lam]
        vals = list_vals[qc].astype(jnp.float32)
        # impact order within each list
        order = jnp.argsort(-vals, axis=-1)[:, :b]
        docs_b = jnp.take_along_axis(docs, order, axis=1)
        vals_b = jnp.take_along_axis(vals, order, axis=1)
        contrib = vals_b * qv[:, None].astype(jnp.float32)
        contrib = jnp.where(qv[:, None] > 0, contrib, 0.0)
        acc = acc.at[jnp.clip(docs_b, 0, n_docs)].add(contrib)
        acc = acc[:n_docs]
        return jax.lax.top_k(acc, k)

    return jax.vmap(one)(queries.coords, queries.vals)
