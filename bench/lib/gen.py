"""Synthetic learned-sparse collections, copied from the program's
``repro.data.synthetic_sparse`` so that later changes to the program
cannot move the benchmark's data.

MS MARCO / NQ embeddings are not available offline, so benchmarks run
on collections synthesized to match the SPLADE statistics the paper
reports (§7.1) and the concentration-of-importance property (§4):

  * vocabulary ~30k with Zipf-like coordinate popularity,
  * docs ~119 nnz, queries ~43 nnz (scaled down proportionally for CPU
    test sizes),
  * log-normal weights -> a heavy-tailed per-vector value profile, so
    the top ~10 query entries / ~50 doc entries carry ~0.75 of the L1
    mass (validated by benchmarks/fig1_concentration.py),
  * a shared topic structure so queries have true near neighbors and
    recall curves are non-trivial.

Each row mixes the affinity profiles of two topics (the secondary at
half weight) and draws its coordinates without replacement by Gumbel
top-k over the union of the two topics' coordinates. Coordinates
outside both topics are never drawn (their weight was e^-30 per
coordinate in the dense formulation this replaces), so a row costs
O(2 * topic_coords) instead of O(dim). Rows are drawn in fixed-size
chunks, each from its own generator seeded by (seed, stream, chunk), at
most ``MAX_THREADS`` chunks at a time, so the result does not depend on
how many threads draw them.

Host footprint: a chunk is worked ``ROW_BLOCK`` rows at a time, so no
temporary exceeds ``ROW_BLOCK`` x 2 * topic_coords float64 (3 MB at MS
MARCO widths), and each worker keeps one ``[CHUNK_ROWS, nnz]`` float64
buffer of picked weights (8 MB at doc nnz 128) for the whole draw: about
40 MB a worker beside the output arrays. The blocks consume each chunk's
stream in the order one whole-chunk draw would (the Gumbel keys of every
row, then the log-normal values of every row), so the rows are the same
bit for bit. Whole-chunk temporaries of 25-50 MB, allocated and freed
many times a chunk, are what this avoids.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 8192      # rows per independently seeded draw
ROW_BLOCK = 512        # rows of a chunk worked at once (temporaries <= 3 MB)
MAX_THREADS = 16       # chunks drawn at once (host memory ~40 MB each)


@dataclasses.dataclass(frozen=True)
class SyntheticSparseConfig:
    dim: int = 4096
    n_docs: int = 8192
    n_queries: int = 256
    doc_nnz: int = 96
    query_nnz: int = 32
    n_topics: int = 64
    topic_coords: int = 384       # candidate coords per topic
    zipf_a: float = 1.05
    value_sigma: float = 1.0      # log-normal sigma -> concentration
    doc_topic_mix: int = 2        # topics mixed per doc
    seed: int = 0


def _sample_rows(rng, logits: np.ndarray, nnz: int):
    """Gumbel top-k: one draw of ``nnz`` distinct indices per row,
    with probability proportional to exp(logits), in descending order
    of the perturbed logit."""
    key = logits + rng.gumbel(size=logits.shape)
    top = np.argpartition(-key, nnz - 1, axis=-1)[:, :nnz]
    order = np.argsort(-np.take_along_axis(key, top, axis=-1), axis=-1)
    return np.take_along_axis(top, order, axis=-1)


class _Topics:
    """Topic coordinate sets, their log-affinities, and a dense
    [n_topics, dim] position map used to find coordinates two topics
    share (O(n_topics * dim), independent of the row count)."""

    def __init__(self, cfg: SyntheticSparseConfig, rng):
        d = cfg.dim
        # Zipf-ish popularity over a shuffled vocabulary
        ranks = rng.permutation(d) + 1
        log_pop = np.log(1.0 / ranks ** cfg.zipf_a)
        self.coords = _sample_rows(
            rng, np.broadcast_to(log_pop, (cfg.n_topics, d)).copy(),
            cfg.topic_coords).astype(np.int32)              # [T, m]
        self.log_w = np.log(rng.lognormal(0.0, cfg.value_sigma,
                                          size=self.coords.shape))
        self.pos = np.full((cfg.n_topics, d), -1, np.int32)
        t = np.arange(cfg.n_topics)[:, None]
        self.pos[t, self.coords] = np.arange(cfg.topic_coords)


def _candidates(topics: _Topics, cfg: SyntheticSparseConfig,
                t1: np.ndarray, t2: np.ndarray, primary_scale: float):
    """Candidate coordinates and logits of rows of topics (t1, t2): the
    primary topic's coords followed by the secondary's; a coordinate
    both share keeps the max of its two logits in the primary half and
    drops out of the secondary half."""
    lg1 = topics.log_w[t1] * primary_scale                  # [n, m]
    cand = topics.coords[t1]
    if cfg.doc_topic_mix > 1:
        lg2 = topics.log_w[t2] * (primary_scale * 0.5)
        shared = topics.pos[t2[:, None], cand]              # [n, m]
        has = shared >= 0
        j = np.where(has, shared, 0)
        lg1 = np.where(has, np.maximum(lg1, np.take_along_axis(lg2, j, 1)),
                       lg1)
        rows, cols = np.nonzero(has)
        lg2[rows, j[rows, cols]] = -np.inf
        cand = np.concatenate([cand, topics.coords[t2]], axis=1)
        lg1 = np.concatenate([lg1, lg2], axis=1)
    return cand, lg1


def _draw_chunk(topics: _Topics, cfg: SyntheticSparseConfig, rng,
                t1: np.ndarray, t2: np.ndarray, nnz: int,
                primary_scale: float, coords: np.ndarray, vals: np.ndarray,
                base: np.ndarray) -> None:
    """One chunk of rows into ``coords`` / ``vals`` (its ``[n, nnz]``
    rows of the output), ``ROW_BLOCK`` rows at a time: every block's
    Gumbel draw first, then every block's log-normal values, each in
    row order, as one whole-chunk draw consumes ``rng``. ``base``
    (``[n, nnz]`` float64) holds the picked weights between the two."""
    n = t1.size
    blocks = [slice(lo, min(lo + ROW_BLOCK, n))
              for lo in range(0, n, ROW_BLOCK)]
    for b in blocks:
        cand, lg = _candidates(topics, cfg, t1[b], t2[b], primary_scale)
        pick = _sample_rows(rng, lg, nnz)                   # [rows, nnz]
        coords[b] = np.take_along_axis(cand, pick, axis=1)
        base[b] = np.exp(np.take_along_axis(lg, pick, axis=1))
    for b in blocks:
        v = base[b] * rng.lognormal(0.0, cfg.value_sigma * 0.5,
                                    size=(b.stop - b.start, nnz))
        vals[b] = v / np.maximum(v.max(axis=-1, keepdims=True), 1e-9) * 3.0


def _draw(topics: _Topics, cfg: SyntheticSparseConfig, stream: int,
          n_rows: int, nnz: int, primary_scale: float):
    """``n_rows`` rows of one stream (0 = docs, 1 = queries)."""
    coords = np.empty((n_rows, nnz), np.int32)
    vals = np.empty((n_rows, nnz), np.float32)
    t1 = np.empty((n_rows,), np.int64)
    worker = threading.local()      # each thread's buffer of picked weights

    def chunk(c: int) -> None:
        rng = np.random.default_rng([cfg.seed, stream, c])
        lo = c * CHUNK_ROWS
        n = min(CHUNK_ROWS, n_rows - lo)
        a = rng.integers(0, cfg.n_topics, n)
        b = rng.integers(0, cfg.n_topics, n)
        if not hasattr(worker, "base"):
            worker.base = np.empty((CHUNK_ROWS, nnz), np.float64)
        _draw_chunk(topics, cfg, rng, a, b, nnz, primary_scale,
                    coords[lo:lo + n], vals[lo:lo + n], worker.base[:n])
        t1[lo:lo + n] = a

    n_chunks = -(-n_rows // CHUNK_ROWS)
    workers = min(n_chunks, MAX_THREADS, len(os.sched_getaffinity(0)))
    if workers <= 1:
        for c in range(n_chunks):
            chunk(c)
    else:
        with ThreadPoolExecutor(workers) as pool:
            list(pool.map(chunk, range(n_chunks)))
    return coords, vals, t1


def make_collection(cfg: SyntheticSparseConfig):
    """(doc coords, doc vals, query coords, query vals): int32 / f32
    host arrays, ``[n_docs, doc_nnz]`` and ``[n_queries, query_nnz]``."""
    if cfg.topic_coords < max(cfg.doc_nnz, cfg.query_nnz):
        raise ValueError(
            f"topic_coords={cfg.topic_coords} must be >= the row nnz "
            f"(doc {cfg.doc_nnz}, query {cfg.query_nnz}): rows draw "
            "distinct coordinates from their topics' coordinates")
    topics = _Topics(cfg, np.random.default_rng(cfg.seed))
    doc_c, doc_v, _ = _draw(topics, cfg, 0, cfg.n_docs, cfg.doc_nnz, 1.0)
    q_c, q_v, _ = _draw(topics, cfg, 1, cfg.n_queries, cfg.query_nnz, 1.3)
    return doc_c, doc_v, q_c, q_v
