"""Seismic index construction (Algorithm 1), jit-compiled.

Pipeline per coordinate i (one inverted list):
  1. static pruning  — keep the lam docs with the largest x_i (§5.1)
  2. geometric blocking — shallow K-Means: sample beta member docs as
     representatives, assign every member to its max-inner-product
     representative (§5.2, [Chierichetti et al. 07])
  3. physical blocks — contiguous runs after the cluster permutation,
     split at ``block_cap`` boundaries
  4. summaries — coordinate-wise max per block (Eq. 2), alpha-mass
     pruned (Def. 3.1), 8-bit quantized (§5.3)
  5. superblocks (cfg.superblock_fanout > 0) — BMP-style coarse tier:
     every ``fanout`` consecutive physical blocks get one summary that
     coordinate-wise dominates its children (round-up requantized), so
     the router can prune whole superblocks before touching per-block
     summaries

TPU adaptation: assignment inner products are computed either by
gathers against densified representatives (``cluster_mode="gather"``,
cheap on CPU) or by scatter-to-dense + one MXU matmul per list
(``cluster_mode="matmul"``). ``build_index`` drives the lists from the
host in fixed-size chunks written in place, and skips coordinates
without postings.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import SeismicConfig, SeismicIndex
from repro.sparse.ops import PaddedSparse, alpha_mass_subvector
from repro.sparse.quant import dequantize_u8, quantize_u8, quantize_u8_ceil


def _sorted_postings(docs: PaddedSparse):
    """Flatten (coord, val, doc) triples and sort by (coord asc, val desc)."""
    n, nnz = docs.coords.shape
    flat_c = docs.coords.reshape(-1)
    flat_v = docs.vals.reshape(-1).astype(jnp.float32)
    flat_d = jnp.repeat(jnp.arange(n, dtype=jnp.int32), nnz)
    # invalid (padding) entries sort to the very end
    flat_c = jnp.where(flat_v > 0, flat_c, docs.dim)
    order = jnp.lexsort((-flat_v, flat_c))
    return flat_c[order], flat_v[order], flat_d[order]


def _prune_list(i, sorted_c, sorted_v, sorted_d, starts, counts, lam, n_docs):
    """Top-lam postings of coordinate i out of the global sorted triples."""
    start = starts[i]
    cnt = jnp.minimum(counts[i], lam)
    idx = start + jnp.arange(lam)
    valid = jnp.arange(lam) < cnt
    docs = jnp.where(valid, jnp.take(sorted_d, idx, mode="clip"), n_docs)
    vals = jnp.where(valid, jnp.take(sorted_v, idx, mode="clip"), 0.0)
    return docs.astype(jnp.int32), vals, cnt.astype(jnp.int32)


def _assign_clusters(key, docs, vals, cnt, fwd, cfg: SeismicConfig):
    """Shallow K-Means over one pruned list.

    Representatives are ``beta`` uniformly sampled members; each member
    goes to the representative maximizing <x, mu> (§5.2).
    """
    lam, beta, d = cfg.lam, cfg.beta, fwd.dim
    pos = jax.random.randint(key, (beta,), 0, jnp.maximum(cnt, 1))
    rep_ids = jnp.take(docs, pos, mode="clip")                     # [beta]
    rep_c = jnp.take(fwd.coords, rep_ids, axis=0, mode="clip")     # [beta, nnz]
    rep_v = jnp.take(fwd.vals, rep_ids, axis=0,
                     mode="clip").astype(jnp.float32)
    # densify representatives: [beta, d]
    rep_dense = jnp.zeros((beta, d), jnp.float32)
    rep_dense = rep_dense.at[jnp.arange(beta)[:, None], rep_c].add(rep_v)

    doc_c = jnp.take(fwd.coords, docs, axis=0, mode="clip")        # [lam, nnz]
    doc_v = jnp.take(fwd.vals, docs, axis=0,
                     mode="clip").astype(jnp.float32)
    if cfg.cluster_mode == "matmul":
        # TPU-native: densify members tile-by-tile and use the MXU.
        doc_dense = jnp.zeros((lam, d), jnp.float32)
        doc_dense = doc_dense.at[jnp.arange(lam)[:, None], doc_c].add(doc_v)
        ips = doc_dense @ rep_dense.T                              # [lam, beta]
    else:
        # gather path: <x, mu> = sum_j mu[x.coords_j] * x.vals_j
        gathered = rep_dense[:, doc_c]                             # [beta, lam, nnz]
        ips = jnp.einsum("bln,ln->lb", gathered, doc_v)
    assign = jnp.argmax(ips, axis=-1).astype(jnp.int32)            # [lam]
    # padding entries sort last
    assign = jnp.where(jnp.arange(lam) < cnt, assign, beta)
    return assign


def _physical_blocks(assign, cnt, cfg: SeismicConfig):
    """Stable-sort by cluster, then split runs at block_cap boundaries."""
    lam, nb = cfg.lam, cfg.n_blocks
    perm = jnp.argsort(assign, stable=True)
    sorted_assign = assign[perm]
    pos = jnp.arange(lam)
    # start-of-cluster flags
    prev = jnp.concatenate([jnp.array([-1], sorted_assign.dtype),
                            sorted_assign[:-1]])
    new_cluster = sorted_assign != prev
    # position within cluster
    cluster_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_cluster, pos, 0))
    within = pos - cluster_start
    new_block = new_cluster | (within % cfg.block_cap == 0)
    # only positions holding real entries form blocks
    new_block = new_block & (pos < cnt)
    block_id = jnp.cumsum(new_block.astype(jnp.int32)) - 1          # [-1 .. nb)
    block_id = jnp.where(pos < cnt, block_id, nb)                   # pad -> sentinel
    blk_len = jnp.bincount(jnp.clip(block_id, 0, nb), length=nb + 1)[:nb]
    blk_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(blk_len)[:-1].astype(jnp.int32)])
    return perm, block_id.astype(jnp.int32), blk_off.astype(jnp.int32), \
        blk_len.astype(jnp.int32)


def _summaries(docs_perm, block_id, fwd, cfg: SeismicConfig):
    """Per-block summary (Eq. 2 coordinate-wise max, or centroid under
    the §6 generalized sketch) -> alpha-mass -> u8 quant."""
    nb, d, s = cfg.n_blocks, fwd.dim, cfg.summary_nnz
    doc_c = jnp.take(fwd.coords, docs_perm, axis=0, mode="clip")    # [lam, nnz]
    doc_v = jnp.take(fwd.vals, docs_perm, axis=0,
                     mode="clip").astype(jnp.float32)
    doc_v = jnp.where(docs_perm[:, None] < fwd.n, doc_v, 0.0)
    dense = jnp.zeros((nb + 1, d), jnp.float32)
    bid = jnp.clip(block_id, 0, nb)
    if cfg.summary_kind == "centroid":
        dense = dense.at[bid[:, None], doc_c].add(doc_v)
        cnt = jnp.zeros((nb + 1,), jnp.float32).at[bid].add(
            (docs_perm < fwd.n).astype(jnp.float32))
        dense = dense / jnp.maximum(cnt, 1.0)[:, None]
    else:  # "max": the conservative Eq. 2 bound
        dense = dense.at[bid[:, None], doc_c].max(doc_v)
    dense = dense[:nb]
    sc, sv = jax.vmap(
        lambda row: alpha_mass_subvector(jnp.arange(d, dtype=jnp.int32),
                                         row, cfg.alpha, s))(dense)
    q, scale, zero = quantize_u8(sv)
    return sc, q, scale, zero


def _superblock_summaries(sc, q, scale, zero, dim: int, cfg: SeismicConfig):
    """Coarse tier over one list's quantized block summaries.

    Groups blocks [0..nb) into ``n_superblocks`` fixed-fanout groups
    (block j -> superblock j // fanout) and takes the coordinate-wise
    max of the DEQUANTIZED child summaries, so the superblock score
    upper-bounds every child score for any nonnegative query — the BMP
    block-max property one level up. Size ``fanout * summary_nnz``
    never truncates the union of child supports, and the round-up
    requantization (:func:`quantize_u8_ceil`) keeps the bound through
    the second quantization (up to float rounding).
    """
    nb, s = q.shape
    f, ns = cfg.superblock_fanout, cfg.n_superblocks
    s2 = min(cfg.superblock_nnz, dim)   # top_k width can't exceed dim
    v = dequantize_u8(q, scale, zero)                       # [nb, S]
    sup_id = jnp.arange(nb, dtype=jnp.int32) // f           # [nb]
    dense = jnp.zeros((ns, dim), jnp.float32)
    dense = dense.at[sup_id[:, None], sc].max(v)            # scatter-max
    vals, coords = jax.lax.top_k(dense, s2)                 # [ns, S2]
    coords = jnp.where(vals > 0, coords, 0)
    q2, scale2, zero2 = quantize_u8_ceil(vals)
    return coords.astype(jnp.int32), q2, scale2, zero2


def list_block_arrays(key_i, docs, vals, cnt, fwd, cfg: SeismicConfig):
    """Cluster + block + summarize ONE pruned list: the per-list half of
    Algorithm 1 after static pruning.

    ``docs``/``vals`` are the pruned postings ([lam], value-descending,
    value ties broken by ascending doc id, sentinel ``fwd.n`` padding)
    and ``key_i`` the per-list PRNG key
    (``fold_in(PRNGKey(cfg.seed), coord)``). This is the seam
    :mod:`repro.core.mutate` reuses for major (per-list) compaction:
    feeding it the merged base+tail members of a list reproduces the
    fresh-build arrays bit-exactly, because ``build_index`` routes
    through the identical call.
    """
    if cfg.blocking == "fixed":
        # Fig. 5 baseline: impact-ordered fixed-size chunks (single
        # cluster; the physical block splitter cuts it at block_cap)
        assign = jnp.where(jnp.arange(cfg.lam) < cnt, 0, cfg.beta)
        assign = assign.astype(jnp.int32)
    else:
        assign = _assign_clusters(key_i, docs, vals, cnt, fwd, cfg)
    perm, block_id, blk_off, blk_len = _physical_blocks(assign, cnt, cfg)
    docs_perm = docs[perm]
    vals_perm = vals[perm]
    sc, q, scale, zero = _summaries(docs_perm, block_id, fwd, cfg)
    out = (docs_perm, vals_perm, cnt, blk_off, blk_len, sc, q, scale, zero)
    if cfg.superblock_fanout > 0:
        out = out + _superblock_summaries(sc, q, scale, zero, fwd.dim, cfg)
    return out


def _build_one_list(i, key, sorted_c, sorted_v, sorted_d, starts, counts,
                    fwd, cfg: SeismicConfig):
    docs, vals, cnt = _prune_list(i, sorted_c, sorted_v, sorted_d,
                                  starts, counts, cfg.lam, fwd.n)
    return list_block_arrays(jax.random.fold_in(key, i), docs, vals, cnt,
                             fwd, cfg)


def block_summaries(docs_perm, block_id, fwd, cfg: SeismicConfig):
    """Public seam over the per-block summary construction (Eq. 2 max ->
    alpha-mass -> u8): compaction computes summaries for freshly
    appended tail blocks through the SAME code path as the builder, so
    an appended block's summary is bit-identical to what a fresh build
    would give the same member set."""
    return _summaries(docs_perm, block_id, fwd, cfg)


def merge_superblock_summary(sup_coords, sup_q, sup_scale, sup_zero,
                             child_sc, child_q, child_scale, child_zero,
                             dim: int, cfg: SeismicConfig):
    """Monotone update of ONE superblock summary with new child blocks.

    Takes the coordinate-wise max of the DEQUANTIZED old superblock
    summary ([S2] + scalars) and the new child block summaries
    ([m, S] + [m]), then round-up requantizes (quantize_u8_ceil). The
    result upper-bounds every child of the group: old children through
    the old superblock (itself an upper bound), new children directly —
    so summaries only ever loosen monotonically under mutation and the
    hierarchical router's pruning stays safe without rebuilding the
    tier.
    """
    s2 = sup_q.shape[-1]
    dense = jnp.zeros((dim,), jnp.float32)
    dense = dense.at[sup_coords].max(
        dequantize_u8(sup_q[None], sup_scale[None], sup_zero[None])[0])
    cv = dequantize_u8(child_q, child_scale, child_zero)       # [m, S]
    dense = dense.at[child_sc.reshape(-1)].max(cv.reshape(-1))
    vals, coords = jax.lax.top_k(dense, s2)
    coords = jnp.where(vals > 0, coords, 0)
    q2, scale2, zero2 = quantize_u8_ceil(vals)
    return coords.astype(jnp.int32), q2, scale2, zero2


def suggest_fanout(n_blocks_stats, *, max_fanout: int = 8) -> int:
    """Adaptive superblock fanout from per-list live-block counts.

    ``n_blocks_stats`` is an array of live (non-empty) physical blocks
    per inverted list — ``(index.block_len > 0).sum(-1)`` for a built
    index, or a modeled estimate at config time. Two-tier routing over
    a list with ``nb`` live blocks costs ``~nb/f`` coarse dots plus
    ``~f`` child dots per kept superblock, so the minimizing fanout
    scales like ``sqrt(nb)``. Lists with <= 2 live blocks pay pure
    superblock overhead (the coarse tier scores as many summaries as
    the flat route would), so collections dominated by them get 0
    (keep flat routing).
    """
    stats = np.asarray(n_blocks_stats, np.float64).reshape(-1)
    live = stats[stats > 0]
    if live.size == 0:
        return 0
    mean = float(live.mean())
    if mean <= 2.0:
        return 0
    return int(np.clip(round(math.sqrt(mean)), 2, max_fanout))


def live_blocks(index: SeismicIndex) -> np.ndarray:
    """Per-list live-block counts of a built index (the
    :func:`suggest_fanout` statistic)."""
    return np.asarray((index.block_len > 0).sum(axis=-1))


class DocBlockMap(NamedTuple):
    """CSR doc -> (list, block) membership over a built index.

    ``lists[indptr[d]:indptr[d+1]]`` / ``blocks[...]`` enumerate every
    (inverted list, physical block) pair holding doc ``d`` after static
    pruning — the structural ground truth the quality plane's loss
    funnel needs to decide whether a missed doc was ever reachable
    through the routed blocks (``repro.obs.quality``).
    """
    indptr: np.ndarray    # i64 [n_docs + 1]
    lists: np.ndarray     # i32 [n_memberships]
    blocks: np.ndarray    # i32 [n_memberships]


def doc_block_map(index: SeismicIndex) -> DocBlockMap:
    """Invert ``list_docs`` into per-doc block memberships (host-side).

    Physical blocks are contiguous position runs per list
    (``block_off`` is the cumsum of ``block_len``), so position ``p``'s
    block is the first block whose end offset exceeds ``p``.
    """
    docs = np.asarray(index.list_docs)                  # [L, lam]
    lens = np.asarray(index.list_len)                   # [L]
    ends = np.asarray(index.block_off) + np.asarray(index.block_len)
    n_docs = index.n_docs
    pos = np.arange(docs.shape[1])
    live = pos[None, :] < lens[:, None]
    live &= docs < n_docs                               # drop pad sentinels
    list_ids, positions = np.nonzero(live)
    block_ids = np.empty(list_ids.size, np.int32)
    for i, (ell, p) in enumerate(zip(list_ids, positions)):
        block_ids[i] = np.searchsorted(ends[ell], p, side="right")
    member_docs = docs[list_ids, positions]
    order = np.argsort(member_docs, kind="stable")
    counts = np.bincount(member_docs, minlength=n_docs)
    indptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return DocBlockMap(indptr, list_ids[order].astype(np.int32),
                       block_ids[order])


@jax.jit
def _postings(docs: PaddedSparse):
    """Sorted (coord, val, doc) triples plus each coordinate's start
    offset and posting count in them."""
    d = docs.dim
    sorted_c, sorted_v, sorted_d = _sorted_postings(docs)
    starts = jnp.searchsorted(sorted_c, jnp.arange(d + 1))
    counts = (starts[1:] - starts[:-1]).astype(jnp.int32)
    return sorted_c, sorted_v, sorted_d, starts[:-1].astype(jnp.int32), \
        counts


@partial(jax.jit, static_argnames=("cfg", "lead"))
def _empty_planes(fwd32: PaddedSparse, cfg: SeismicConfig, lead: tuple):
    """Every per-list plane, filled with what the per-list build gives
    a coordinate without postings (the same arrays for every such
    coordinate: nothing in it depends on the coordinate)."""
    lam = cfg.lam
    empty = list_block_arrays(
        jax.random.PRNGKey(cfg.seed), jnp.full((lam,), fwd32.n, jnp.int32),
        jnp.zeros((lam,), jnp.float32), jnp.int32(0), fwd32, cfg)
    return tuple(jnp.broadcast_to(x, lead + (fwd32.dim,) + x.shape)
                 for x in empty)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def _fill_lists(planes, ids, postings, fwd32: PaddedSparse,
                cfg: SeismicConfig):
    """Build the lists ``ids`` and write them into ``planes`` (donated:
    updated in place, one row slice per list — a scatter would make XLA
    copy whole planes). Repeated ids write identical rows."""
    key = jax.random.PRNGKey(cfg.seed)
    built = jax.vmap(lambda i: _build_one_list(i, key, *postings, fwd32,
                                               cfg))(ids)
    lead = planes[0].ndim - built[0].ndim
    out = []
    for p, b in zip(planes, built):
        for j in range(ids.shape[0]):
            row = b[j][(None,) * (lead + 1)]
            start = (0,) * lead + (ids[j],) + (0,) * (row.ndim - lead - 1)
            p = jax.lax.dynamic_update_slice(p, row.astype(p.dtype), start)
        out.append(p)
    return tuple(out)


@partial(jax.jit, static_argnames=("cfg", "lead"))
def _forward_plane(docs: PaddedSparse, cfg: SeismicConfig, lead: tuple):
    """The served forward plane (and its u8 dequant constants)."""
    fwd_scale = fwd_zero = None
    if cfg.fwd_quant:
        # compact forward index: u8 values (per-doc affine) + u16 coords
        q, fwd_scale, fwd_zero = quantize_u8(docs.vals.astype(jnp.float32))
        cdt = jnp.uint16 if docs.dim < 65536 else jnp.int32
        fwd = PaddedSparse(docs.coords.astype(cdt), q, docs.dim)
    else:
        fwd = docs.astype(jnp.dtype(cfg.fwd_dtype))
    out = (fwd, fwd_scale, fwd_zero)
    return jax.tree.map(lambda x: x.reshape(lead + x.shape), out)


def index_shape(n_docs: int, dim: int, doc_nnz: int,
                cfg: SeismicConfig) -> SeismicIndex:
    """The ``SeismicIndex`` that ``build_index`` returns for an
    ``[n_docs, doc_nnz]`` f32 collection, as ``ShapeDtypeStruct``
    leaves — for sizing and compiling without building."""
    docs = PaddedSparse(jax.ShapeDtypeStruct((n_docs, doc_nnz), jnp.int32),
                        jax.ShapeDtypeStruct((n_docs, doc_nnz), jnp.float32),
                        dim)

    def assemble(docs):
        planes = _empty_planes(docs, cfg, ())
        fwd, fwd_scale, fwd_zero = _forward_plane(docs, cfg, ())
        sup = planes[9:] if cfg.superblock_fanout > 0 else (None,) * 4
        return SeismicIndex(fwd, *planes[:9], fwd_scale=fwd_scale,
                            fwd_zero=fwd_zero, sup_coords=sup[0],
                            sup_q=sup[1], sup_scale=sup[2],
                            sup_zero=sup[3], config=cfg)

    return jax.eval_shape(assemble, docs)


def build_index(docs: PaddedSparse, cfg: SeismicConfig = SeismicConfig(),
                *, list_chunk: int = 64, lead: tuple = (),
                lists=None) -> SeismicIndex:
    """Algorithm 1 over the whole collection, on the device that holds
    ``docs``.

    Lists are built ``list_chunk`` coordinates per jitted launch and
    written in place into preallocated planes, so peak device memory is
    the index plus one chunk's temporaries (``list_chunk * n_blocks *
    dim`` floats and the cluster-assignment intermediates). Coordinates
    without postings are not built: they keep the arrays the per-list
    build gives an empty list, so the result is the same as building
    every coordinate. ``lead`` prepends unit axes to every array leaf
    (``(1,)`` gives one shard of a stacked, sharded index without a
    copy).

    ``lists`` (coordinates) restricts the build to those lists; the
    others stay empty. A query reads only the lists of its top-``cut``
    coordinates (``retrieval.prep.probed_lists``), so such an index
    answers the queries whose probed lists it holds exactly as the full
    index does."""
    fwd32 = docs.astype(jnp.float32)
    postings = _postings(docs)
    live = np.flatnonzero(np.asarray(postings[4]) > 0)
    if lists is not None:
        live = np.intersect1d(live, np.asarray(lists))
    planes = _empty_planes(fwd32, cfg, lead)
    for lo in range(0, live.size, list_chunk):
        ids = live[lo:lo + list_chunk]
        ids = np.pad(ids, (0, list_chunk - ids.size), mode="edge")
        planes = _fill_lists(planes, jnp.asarray(ids, jnp.int32),
                             postings, fwd32, cfg)
    del postings
    (list_docs, list_vals, list_len, blk_off, blk_len,
     sum_coords, sum_q, sum_scale, sum_zero) = planes[:9]
    sup_coords = sup_q = sup_scale = sup_zero = None
    if cfg.superblock_fanout > 0:
        sup_coords, sup_q, sup_scale, sup_zero = planes[9:]
    fwd, fwd_scale, fwd_zero = _forward_plane(docs, cfg, lead)
    return SeismicIndex(
        fwd=fwd, list_docs=list_docs, list_vals=list_vals,
        list_len=list_len, block_off=blk_off, block_len=blk_len,
        sum_coords=sum_coords, sum_q=sum_q, sum_scale=sum_scale,
        sum_zero=sum_zero, fwd_scale=fwd_scale, fwd_zero=fwd_zero,
        sup_coords=sup_coords, sup_q=sup_q, sup_scale=sup_scale,
        sup_zero=sup_zero, config=cfg)
