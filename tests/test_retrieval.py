"""The staged batch-first retrieval pipeline and its natively-batched
Pallas kernels (interpret-mode parity vs refs), plus the cross-stage
invariants the autotuner leans on: ``merge_topk`` permutation /
sentinel-duplicate invariance and k>C clamp edges, and selector
policies returning fixed shapes under jit. Hypothesis hardens the
merge properties when installed; the deterministic sweeps run always.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from helpers import given, needs_hypothesis, settings, st

from repro.kernels.gather_dot.ops import gather_dot, gather_dot_batch
from repro.kernels.gather_dot.ref import gather_dot_batch_ref, gather_dot_ref
from repro.kernels.summary_dot.ops import summary_dot, summary_dot_batch
from repro.kernels.summary_dot.ref import (summary_dot_batch_ref,
                                           summary_dot_ref)
from repro.retrieval import (SearchParams, get_selector, register_selector,
                             search_pipeline, selector_names)
from repro.sparse.quant import quantize_u8


# ------------------------------------------------- batched gather_dot

@pytest.mark.parametrize("qn,n,nnz,d", [
    (8, 128, 16, 512),     # exact tile multiples
    (3, 37, 17, 300),      # neither Q nor N tile-aligned
    (1, 5, 8, 64),         # tiny single-query batch
    (13, 260, 33, 1000),   # N just past two tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_dot_batch_parity(qn, n, nnz, d, dtype):
    rng = np.random.default_rng(qn * n + nnz)
    q = jnp.asarray(rng.lognormal(0, 1, (qn, d)), dtype)
    coords = jnp.asarray(rng.integers(0, d, (qn, n, nnz)), jnp.int32)
    vals = jnp.asarray(rng.lognormal(0, 1, (qn, n, nnz)), dtype)
    got = gather_dot_batch(q, coords, vals)
    want = gather_dot_batch_ref(q, coords, vals)
    rtol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol)


def test_gather_dot_batch_fused_dequant_parity():
    """Compact-forward-index path: u8 values + per-candidate (scale,
    zero) dequantized inside the kernel."""
    rng = np.random.default_rng(0)
    qn, n, nnz, d = 5, 70, 24, 777
    q = jnp.asarray(rng.lognormal(0, 1, (qn, d)), jnp.float32)
    coords = jnp.asarray(rng.integers(0, d, (qn, n, nnz)), jnp.int32)
    vals = rng.lognormal(0, 1, (qn, n, nnz)).astype(np.float32)
    vals[rng.random((qn, n, nnz)) < 0.25] = 0.0    # padded entries
    u8, scale, zero = quantize_u8(jnp.asarray(vals))
    got = gather_dot_batch(q, coords, u8, scale, zero)
    want = gather_dot_batch_ref(q, coords, u8, scale, zero)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gather_dot_legacy_single_query_api():
    """The pre-batch [N, nnz] API still matches its ref (Q=1 reshape)."""
    rng = np.random.default_rng(1)
    n, nnz, d = 37, 12, 400
    q = jnp.asarray(rng.lognormal(0, 1, d), jnp.float32)
    coords = jnp.asarray(rng.integers(0, d, (n, nnz)), jnp.int32)
    vals = jnp.asarray(rng.lognormal(0, 1, (n, nnz)), jnp.float32)
    np.testing.assert_allclose(np.asarray(gather_dot(q, coords, vals)),
                               np.asarray(gather_dot_ref(q, coords, vals)),
                               rtol=1e-6)


# ------------------------------------------------ batched summary_dot

@pytest.mark.parametrize("qn,l,s,d", [
    (8, 128, 32, 1024),    # exact tile multiples
    (3, 45, 12, 300),      # odd everything
    (1, 1, 8, 64),         # single query, single block
    (9, 200, 24, 2048),    # L between tile multiples
])
def test_summary_dot_batch_parity(qn, l, s, d):
    rng = np.random.default_rng(qn + l)
    q = jnp.asarray(rng.lognormal(0, 1, (qn, d)), jnp.float32)
    coords = jnp.asarray(rng.integers(0, d, (qn, l, s)), jnp.int32)
    vals = rng.lognormal(0, 1, (qn, l, s)).astype(np.float32)
    vals[rng.random((qn, l, s)) < 0.3] = 0.0       # padding
    u8, scale, zero = quantize_u8(jnp.asarray(vals))
    got = summary_dot_batch(q, coords, u8, scale, zero)
    want = summary_dot_batch_ref(q, coords, u8, scale, zero)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_summary_dot_batch_all_padding_rows():
    """Summaries that are 100% padding (level 0) must score exactly 0."""
    rng = np.random.default_rng(2)
    qn, l, s, d = 4, 20, 16, 256
    q = jnp.asarray(rng.lognormal(0, 1, (qn, d)), jnp.float32)
    coords = jnp.asarray(rng.integers(0, d, (qn, l, s)), jnp.int32)
    u8 = jnp.zeros((qn, l, s), jnp.uint8)
    scale = jnp.asarray(rng.random((qn, l)), jnp.float32)
    zero = jnp.asarray(rng.random((qn, l)), jnp.float32)
    got = np.asarray(summary_dot_batch(q, coords, u8, scale, zero))
    np.testing.assert_array_equal(got, np.zeros((qn, l), np.float32))


def test_summary_dot_legacy_single_query_api():
    rng = np.random.default_rng(3)
    cut, nb, s, d = 5, 9, 16, 512
    q = jnp.asarray(rng.lognormal(0, 1, d), jnp.float32)
    coords = jnp.asarray(rng.integers(0, d, (cut, nb, s)), jnp.int32)
    vals = rng.lognormal(0, 1, (cut, nb, s)).astype(np.float32)
    u8, scale, zero = quantize_u8(jnp.asarray(vals))
    got = summary_dot(q, coords, u8, scale, zero)
    want = summary_dot_ref(q, coords, u8, scale, zero)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- scorer stage masks

def test_score_candidates_sentinel_padding(small_index):
    """Sentinel candidate ids (== n_docs) must score -inf on both the
    jnp and the kernel path; real ids must agree across paths."""
    from repro.retrieval.scorer import score_candidates
    idx, _ = small_index
    rng = np.random.default_rng(4)
    qn, c = 3, 40
    q_dense = jnp.asarray(rng.lognormal(0, 1, (qn, idx.dim)), jnp.float32)
    cand = rng.integers(0, idx.n_docs, (qn, c))
    cand[:, ::3] = idx.n_docs                       # sentinel-padded slots
    cand = jnp.asarray(cand, jnp.int32)
    s_jnp = np.asarray(score_candidates(idx, q_dense, cand, False))
    s_krn = np.asarray(score_candidates(idx, q_dense, cand, True))
    assert (s_jnp[:, ::3] == -np.inf).all()
    np.testing.assert_allclose(s_jnp, s_krn, rtol=1e-5, atol=1e-5)


# ------------------------------------------ pipeline + selector registry

def test_selector_registry():
    assert set(selector_names()) >= {"budget", "adaptive",
                                     "global_threshold"}
    with pytest.raises(KeyError, match="unknown selector"):
        get_selector("nope")

    @register_selector("_test_probe")
    def probe(index, batch, p):     # pragma: no cover - registry only
        return None

    assert get_selector("_test_probe") is probe


@pytest.mark.parametrize("policy", ["budget", "adaptive",
                                    "global_threshold"])
def test_pipeline_policies_recall(small_index, small_collection, policy):
    from repro.core.baselines import exact_search
    from repro.core.oracle import recall_at_k
    idx, _ = small_index
    docs, queries, *_ = small_collection
    p = SearchParams(k=10, cut=8, block_budget=48, policy=policy)
    _, ids, ev = search_pipeline(idx, queries, p)
    _, eids = exact_search(docs, queries, 10)
    rec = np.mean([recall_at_k(np.asarray(ids[q]), np.asarray(eids[q]))
                   for q in range(queries.n)])
    assert rec >= 0.9, (policy, rec)
    assert np.asarray(ev).mean() < 0.5 * docs.n


def test_global_threshold_prunes_vs_budget(small_index, small_collection):
    """The BMP-style selector must evaluate fewer docs than exhaustive
    budget routing at the same block budget."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    pb = SearchParams(k=10, cut=8, block_budget=48, policy="budget")
    pg = SearchParams(k=10, cut=8, block_budget=48,
                      policy="global_threshold")
    _, _, evb = search_pipeline(idx, queries, pb)
    _, _, evg = search_pipeline(idx, queries, pg)
    assert np.asarray(evg).mean() < np.asarray(evb).mean()


def test_pipeline_kernel_path_matches_jnp(small_index, small_collection):
    """use_kernel=True (batched Pallas, interpret mode on CPU) must
    reproduce the jnp path bit-for-bit on ids and near-exactly on
    scores."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    p0 = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive")
    p1 = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive",
                      use_kernel=True)
    s0, i0, e0 = search_pipeline(idx, queries, p0)
    s1, i1, e1 = search_pipeline(idx, queries, p1)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


# --------------------------------------- query-weight lookup by backend

def _jaxpr_text(idx, queries, p) -> str:
    """The unjitted pipeline's jaxpr, traced afresh (a cached jit trace
    would not see a monkeypatched backend)."""
    from repro.retrieval import run_pipeline
    return str(jax.make_jaxpr(
        lambda c, v: run_pipeline(idx, c, v, p))(queries.coords,
                                                 queries.vals))


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("use_kernel", [None, True, False],
                         ids=["auto", "kernels", "gather"])
def test_use_kernel_resolves_by_backend(monkeypatch, small_index,
                                        small_collection, tpu, use_kernel):
    """``use_kernel=None`` takes the pair-match kernels on a TPU and
    the XLA gather elsewhere; an explicit True / False holds on both.
    The router's and the scorer's kernels come and go together."""
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "on_tpu", lambda: tpu)
    want = tpu if use_kernel is None else use_kernel
    assert runtime.default_use_kernel(use_kernel) is want
    idx, _ = small_index
    _, queries, *_ = small_collection
    text = _jaxpr_text(idx, queries, SearchParams(
        k=10, cut=8, block_budget=32, policy="adaptive",
        use_kernel=use_kernel))
    assert ("summary_dot_batch" in text) == want
    assert ("gather_dot_batch" in text) == want


def test_search_params_default_to_backend_lookup():
    """The default and every constructor that derives params leave the
    lookup to the backend."""
    from repro.tune.policy import TunedPolicy
    assert SearchParams().use_kernel is None
    assert TunedPolicy(target=0.9).to_params().use_kernel is None


@pytest.fixture(scope="module")
def msmarco_width_index():
    """A cut-down index at the benchmark's widths: vocabulary 30522,
    doc / query nnz 128 / 48, summary width 96, block_cap 64; lists
    shortened (lam 256, beta 8) and built only where the queries
    probe."""
    from repro.core import SeismicConfig, build_index
    from repro.data import SyntheticSparseConfig, make_collection
    from repro.retrieval.prep import probed_lists
    from repro.sparse.ops import PaddedSparse
    cfg = SyntheticSparseConfig(dim=30522, n_docs=4096, n_queries=8,
                                doc_nnz=128, query_nnz=48, n_topics=16,
                                topic_coords=512, seed=11)
    docs_np, q_np, _ = make_collection(cfg)
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), cfg.dim)
    queries = PaddedSparse(jnp.asarray(q_np.coords),
                           jnp.asarray(q_np.vals), cfg.dim)
    lists = probed_lists(q_np.coords, q_np.vals, cfg.dim, 10)
    idx = build_index(docs, SeismicConfig(lam=256, beta=8, alpha=0.4,
                                          block_cap=64, summary_nnz=96,
                                          fwd_dtype="bfloat16"),
                      list_chunk=16, lists=lists)
    return idx, queries


@pytest.mark.parametrize("budget", [64, 96], ids=["r90", "r97"])
def test_kernel_lookup_matches_gather_at_msmarco_widths(
        msmarco_width_index, budget):
    """At the benchmark's widths and budgets (adaptive policy) the
    pair-match kernels (interpret mode) serve what the XLA gather
    serves: the same ids and docs evaluated, scores to 1e-6."""
    idx, queries = msmarco_width_index
    p = SearchParams(k=10, cut=10, block_budget=budget, policy="adaptive")
    s0, i0, e0 = search_pipeline(idx, queries,
                                 dataclasses.replace(p, use_kernel=False))
    s1, i1, e1 = search_pipeline(idx, queries,
                                 dataclasses.replace(p, use_kernel=True))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    assert (np.asarray(e0) > 256).all()   # candidates of several lists
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6)


@pytest.mark.parametrize("policy", ["budget", "adaptive",
                                    "global_threshold"])
def test_pipeline_fuse_levels_bitexact(small_index, small_collection,
                                       policy):
    """The fuse_level ladder (0 = unfused, 1 = candidate compaction +
    candidate-driven scorer kernel, 2 = + fused router) must be
    BITWISE identical on scores, ids, and docs_evaluated — fusion
    reshapes execution, never results (tests/test_fusion.py carries
    the stage-level and hierarchical/refined variants)."""
    import dataclasses
    idx, _ = small_index
    _, queries, *_ = small_collection
    p0 = SearchParams(k=10, cut=8, block_budget=32, policy=policy)
    outs = [search_pipeline(idx, queries,
                            dataclasses.replace(p0, fuse_level=lvl))
            for lvl in (0, 1, 2)]
    for lvl_out in outs[1:]:
        for x, y in zip(outs[0], lvl_out):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_adaptive_small_block_budget(small_index, small_collection):
    """block_budget < probe_budget must degrade to pure budget routing,
    not crash on a negative stage-2 top_k."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    p = SearchParams(k=5, cut=8, block_budget=4, probe_budget=8,
                     policy="adaptive")
    s, ids, ev = search_pipeline(idx, queries, p)
    assert ids.shape == (queries.n, 5)
    assert (np.asarray(ev) > 0).all()


def test_pipeline_compact_fwd_index_kernel_parity():
    """fwd_quant=True: the scorer's in-kernel u8 dequant must agree
    with the jnp dequant path through the whole pipeline."""
    from repro.core import SeismicConfig, build_index
    from repro.data import SyntheticSparseConfig, make_collection
    from repro.sparse.ops import PaddedSparse
    cfg = SyntheticSparseConfig(dim=512, n_docs=1024, n_queries=8,
                                doc_nnz=32, query_nnz=12, n_topics=16,
                                topic_coords=96, seed=5)
    docs_np, queries_np, _ = make_collection(cfg)
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), docs_np.dim)
    queries = PaddedSparse(jnp.asarray(queries_np.coords),
                           jnp.asarray(queries_np.vals), queries_np.dim)
    idx = build_index(docs, SeismicConfig(lam=96, beta=8, alpha=0.4,
                                          block_cap=24, summary_nnz=24,
                                          fwd_quant=True), list_chunk=16)
    assert idx.fwd_scale is not None
    p0 = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive")
    p1 = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive",
                      use_kernel=True)
    s0, i0, _ = search_pipeline(idx, queries, p0)
    s1, i1, _ = search_pipeline(idx, queries, p1)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-4, atol=1e-4)


def test_search_batch_is_pipeline(small_index, small_collection):
    """The core.query compatibility shim must be the shared pipeline."""
    from repro.core import search_batch
    idx, _ = small_index
    _, queries, *_ = small_collection
    p = SearchParams(k=5, cut=8, block_budget=16, policy="budget")
    s0, i0, e0 = search_batch(idx, queries, p)
    s1, i1, e1 = search_pipeline(idx, queries, p)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


# ------------------------------------------------------- merge guards

def test_merge_topk_k_wider_than_candidates():
    """k > C must clamp to the candidate axis and pad with -1 / -inf."""
    from repro.retrieval import merge_topk
    cand = jnp.array([[3, 7, 9], [2, 4, 11]], jnp.int32)   # 11 = sentinel
    scores = jnp.array([[1.0, 3.0, 2.0], [5.0, -jnp.inf, -jnp.inf]])
    top_s, ids, ev = merge_topk(cand, scores, k=6, n_docs=11)
    assert top_s.shape == (2, 6) and ids.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(ids[0]), [7, 9, 3, -1, -1, -1])
    np.testing.assert_array_equal(np.asarray(ids[1]), [2, -1, -1, -1, -1, -1])
    assert np.asarray(top_s)[0, :3].tolist() == [3.0, 2.0, 1.0]
    assert (np.asarray(top_s)[:, 3:] == -np.inf).all()
    np.testing.assert_array_equal(np.asarray(ev), [3, 2])


def test_pipeline_tiny_block_budget_large_k(small_index, small_collection):
    """block_budget * block_cap < k must not crash the pipeline."""
    idx, icfg = small_index
    _, queries, *_ = small_collection
    p = SearchParams(k=2 * icfg.block_cap, cut=8, block_budget=1,
                     policy="budget")
    s, ids, _ = search_pipeline(idx, queries, p)
    assert ids.shape == (queries.n, 2 * icfg.block_cap)
    assert (np.asarray(ids)[:, -1] == -1).all()   # padded tail


# ----------------------- merge invariants the autotuner leans on
#
# The tuner's cost/recall measurements are only order-invariant and
# reproducible if the merge stage itself is: a permutation of the
# candidate axis, or extra sentinel-masked duplicate slots (exactly
# what dedupe_batch and the refine stage emit), must not change the
# merged top-k nor docs_evaluated.

def _random_merge_inputs(seed, qn=3, c=24, n_docs=100):
    rng = np.random.default_rng(seed)
    cand = rng.integers(0, n_docs, (qn, c)).astype(np.int32)
    sent = rng.random((qn, c)) < 0.2
    cand[sent] = n_docs                              # sentinel slots
    # distinct scores (ties would make the top-k order depend on input
    # position — the pipeline never ties exactly except at -inf)
    scores = np.empty((qn, c), np.float32)
    for q in range(qn):
        scores[q] = rng.permutation(np.arange(c, dtype=np.float32))
    scores[sent] = -np.inf
    return cand, scores, n_docs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_topk_permutation_invariant(seed):
    from repro.retrieval import merge_topk
    cand, scores, n_docs = _random_merge_inputs(seed)
    perm = np.random.default_rng(seed + 100).permutation(cand.shape[1])
    s0, i0, e0 = merge_topk(jnp.asarray(cand), jnp.asarray(scores),
                            10, n_docs)
    s1, i1, e1 = merge_topk(jnp.asarray(cand[:, perm]),
                            jnp.asarray(scores[:, perm]), 10, n_docs)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


@pytest.mark.parametrize("seed", [3, 4])
def test_merge_topk_sentinel_duplicate_slots_invariant(seed):
    """Appending masked duplicate slots (sentinel id, -inf score — what
    dedupe_batch turns repeated candidates into) must change nothing:
    not the top-k, not docs_evaluated."""
    from repro.retrieval import merge_topk
    cand, scores, n_docs = _random_merge_inputs(seed)
    qn, c = cand.shape
    extra = 7
    cand2 = np.concatenate(
        [cand, np.full((qn, extra), n_docs, np.int32)], axis=1)
    scores2 = np.concatenate(
        [scores, np.full((qn, extra), -np.inf, np.float32)], axis=1)
    s0, i0, e0 = merge_topk(jnp.asarray(cand), jnp.asarray(scores),
                            10, n_docs)
    s1, i1, e1 = merge_topk(jnp.asarray(cand2), jnp.asarray(scores2),
                            10, n_docs)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))


@needs_hypothesis
@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 40), st.integers(1, 32))
def test_hypothesis_merge_topk_invariants(seed, k, c):
    """Random k/C (including k > C clamp edges): permutation and
    sentinel-slot invariance plus the [Q, k] padding contract."""
    from repro.retrieval import merge_topk
    cand, scores, n_docs = _random_merge_inputs(seed, qn=2, c=c)
    perm = np.random.default_rng(seed ^ 0x5EED).permutation(c)
    s0, i0, e0 = merge_topk(jnp.asarray(cand), jnp.asarray(scores),
                            k, n_docs)
    s1, i1, e1 = merge_topk(jnp.asarray(cand[:, perm]),
                            jnp.asarray(scores[:, perm]), k, n_docs)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    assert i0.shape == (2, k) and s0.shape == (2, k)
    if k > c:                                   # clamped: padded tail
        assert (np.asarray(i0)[:, c:] == -1).all()
        assert (np.asarray(s0)[:, c:] == -np.inf).all()
    ids = np.asarray(i0)
    assert ((ids == -1) | (ids < n_docs)).all()  # sentinels never leak


def test_selectors_fixed_shapes_under_jit(small_index, small_collection):
    """Every registered selector policy must produce a fixed-shape
    Selection ([Q, block_budget]) under jit — the tuner swaps policies
    as static args and relies on no data-dependent shapes anywhere."""
    from repro.retrieval.prep import prep_queries
    from repro.retrieval.router import route_batch
    idx, _ = small_index
    _, queries, *_ = small_collection
    budget = 12
    for name in selector_names():
        if name.startswith("_"):                # test-registered probes
            continue
        p = SearchParams(k=10, cut=8, block_budget=budget, policy=name)
        select = get_selector(name)
        q_dense, lists, _ = prep_queries(queries.coords, queries.vals,
                                         idx.dim, p.cut)
        batch = route_batch(idx, q_dense, lists, p)
        sel = jax.eval_shape(
            lambda b, _f=select: _f(idx, b, p), batch)
        assert sel.blocks.shape == (queries.n, budget), name
        assert sel.block_scores.shape == (queries.n, budget), name
        # and the traced stage agrees with the abstract eval
        out = jax.jit(lambda b, _f=select: _f(idx, b, p))(batch)
        assert out.blocks.shape == (queries.n, budget), name
