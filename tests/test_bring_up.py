"""What running at MS MARCO scale on a chip rests on, checked small on
the CPU: the chunked data generator, the chunked exact oracle, the
list-restricted build, and chip_smoke.py refusing to run without a TPU.
"""
import dataclasses
import os
import shutil
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import SeismicConfig, SearchParams, build_index
from repro.core.baselines import exact_search
from repro.core.oracle import exact_topk
from repro.data import SyntheticSparseConfig, make_collection
from repro.data import synthetic_sparse
from repro.retrieval import search_pipeline
from repro.retrieval.prep import probed_lists
from repro.sparse.ops import PaddedSparse

from helpers import REPO

SMALL = SyntheticSparseConfig(dim=512, n_docs=1000, n_queries=13,
                              doc_nnz=32, query_nnz=12, n_topics=16,
                              topic_coords=96, seed=5)


def _device(ps):
    return PaddedSparse(jnp.asarray(ps.coords), jnp.asarray(ps.vals), ps.dim)


# ------------------------------------------------------------ generator

def test_make_collection_deterministic_per_seed():
    a_docs, a_q, a_meta = make_collection(SMALL)
    b_docs, b_q, b_meta = make_collection(SMALL)
    for x, y in [(a_docs.coords, b_docs.coords), (a_docs.vals, b_docs.vals),
                 (a_q.coords, b_q.coords), (a_q.vals, b_q.vals),
                 (a_meta["doc_topics"], b_meta["doc_topics"])]:
        np.testing.assert_array_equal(x, y)
    c_docs, _, _ = make_collection(dataclasses.replace(SMALL, seed=6))
    assert not np.array_equal(a_docs.coords, c_docs.coords)


def test_make_collection_rows_have_distinct_coords():
    cfg = dataclasses.replace(SMALL, n_docs=2 * synthetic_sparse.CHUNK_ROWS
                              + 7)
    docs, queries, _ = make_collection(cfg)
    for ps, nnz in [(docs, cfg.doc_nnz), (queries, cfg.query_nnz)]:
        assert ps.coords.shape[1] == nnz
        s = np.sort(ps.coords, axis=1)
        assert not (s[:, 1:] == s[:, :-1]).any()
        assert ((ps.coords >= 0) & (ps.coords < cfg.dim)).all()
        assert (ps.vals > 0).all()


def test_make_collection_memory_is_bounded_by_the_chunk():
    # the chunk is a module constant, not a config knob
    assert "chunk" not in {f.name for f in
                           dataclasses.fields(SyntheticSparseConfig)}
    cfg = SyntheticSparseConfig(dim=30522, n_docs=20000, n_queries=8,
                                doc_nnz=32, query_nnz=16, n_topics=16,
                                topic_coords=64, seed=1)
    tracemalloc.start()
    make_collection(cfg)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    dense = cfg.n_docs * cfg.dim * 8        # one f64 logit per (row, coord)
    assert peak < dense / 20, (peak, dense)


def test_make_collection_rejects_topics_narrower_than_rows():
    with pytest.raises(ValueError, match="topic_coords"):
        make_collection(dataclasses.replace(SMALL, topic_coords=8))


# --------------------------------------------------------------- oracle

@pytest.mark.parametrize("doc_chunk", [1, 64, 333])
def test_exact_search_chunked_equals_unchunked(doc_chunk):
    docs_np, q_np, _ = make_collection(SMALL)
    docs, queries = _device(docs_np), _device(q_np)
    whole_s, whole_i = exact_search(docs, queries, 10, doc_chunk=SMALL.n_docs)
    s, i = exact_search(docs, queries, 10, doc_chunk=doc_chunk)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(whole_i))
    np.testing.assert_allclose(np.asarray(s), np.asarray(whole_s),
                               rtol=1e-6)
    for q in range(SMALL.n_queries):
        want_s, want_i = exact_topk(docs_np.coords, docs_np.vals, SMALL.dim,
                                    q_np.coords[q], q_np.vals[q], 10)
        np.testing.assert_array_equal(np.asarray(whole_i[q]), want_i)


# --------------------------------------------------- list-restricted build

@pytest.mark.parametrize("use_kernel", [False, True])
def test_probed_list_build_answers_like_full_build(use_kernel):
    docs_np, q_np, _ = make_collection(SMALL)
    docs, queries = _device(docs_np), _device(q_np)
    cfg = SeismicConfig(lam=96, beta=8, alpha=0.4, block_cap=24,
                        summary_nnz=24)
    p = SearchParams(k=10, cut=6, block_budget=16, use_kernel=use_kernel)
    lists = probed_lists(q_np.coords, q_np.vals, SMALL.dim, p.cut)
    full = build_index(docs, cfg, list_chunk=16)
    part = build_index(docs, cfg, list_chunk=16, lists=lists)
    assert int((np.asarray(part.list_len) > 0).sum()) \
        <= lists.size < SMALL.dim
    for got, want in zip(search_pipeline(part, queries, p),
                         search_pipeline(full, queries, p)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ chip_smoke

def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_refuses_the_cpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
