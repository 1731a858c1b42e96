"""Stage 6 — refine: kNN-graph neighbor expansion + exact rescore.

The inverted index trades recall for speed at small ``block_budget``:
near-miss documents fall outside the selected blocks even though they
sit right next to retrieved documents in embedding space. The
refinement stage (Bruch et al. 2025, arXiv 2501.11628; guided
traversal of Mallia et al. 2022) recovers them without touching the
inverted index again:

    1. gather the graph neighbors of the current merged top-k
       (``knn_ids``), giving ``[Q, k * graph_degree]`` candidates;
    2. dedupe — among the expansion (``scorer.dedupe_batch``) and
       against every id scored in any earlier round or the original
       merge (sentinel masking), so no document is rescored twice and
       only the genuinely new frontier pays scoring work;
    3. exactly rescore the survivors through the scorer stage's
       ``score_candidates`` — the SAME forward plane and batched
       ``gather_dot`` kernel as phase S (u8 dequant fused on a compact
       forward index), so merged scores are consistent across stages;
    4. re-merge to top-k; repeat ``refine_rounds`` times.

Score consistency in step 3 is load-bearing: rescoring through any
*other* value plane (e.g. an independently quantized copy) mixes two
score scales in one merge, and quantization-inflated imposters can
displace exactly-scored true positives — refinement would then LOSE
recall at high-recall operating points. Scoring through the scorer's
plane makes the merged objective uniform, so the candidate pool only
ever grows under it and recall@k is monotone non-decreasing in
``refine_rounds`` (up to exact score ties).

``refine_rounds == 0`` or ``graph_degree == 0`` is a bit-exact no-op:
the stage returns its inputs untouched at trace time, so pipelines
without the knob compile to the PR 3 program unchanged.

``SearchParams.fuse_level`` changes execution, not results: level 1
compacts each round's frontier before the candidate-driven scoring
kernel (sentinel tiles skipped); level 2 fuses the whole round —
expand, dedupe, seen-mask, compact, rescore — into one Pallas launch
(:mod:`repro.kernels.refine_fused`), so the ``[Q, k * graph_degree]``
expansion is never materialized in HBM. All levels are bit-exact.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.retrieval.params import SearchParams

if TYPE_CHECKING:  # annotation-only: keeps repro.graph import-cycle-free
    from repro.core.types import SeismicIndex


def validate_refine_params(index: SeismicIndex, p: SearchParams) -> None:
    """Fail fast when the refinement knobs don't match the index."""
    if p.graph_degree <= 0:
        return
    if index.knn_ids is None:
        raise ValueError(
            f"graph refinement requested (graph_degree={p.graph_degree}) "
            "but the index has no kNN graph; attach one with "
            "repro.graph.build_doc_graph")
    built = index.knn_ids.shape[1]
    if p.graph_degree > built:
        raise ValueError(
            f"graph_degree={p.graph_degree} exceeds the built graph "
            f"degree {built}; rebuild with a larger degree or lower the "
            "knob (neighbors are score-ordered, so any prefix is valid)")


def expand_neighbors(index: SeismicIndex, ids: jax.Array,
                     degree: int) -> jax.Array:
    """Graph neighbors of the current top-k -> [Q, k * degree] doc ids.

    ``ids`` carries -1 padding; padded rows expand to the sentinel
    ``n_docs``. Neighbors are stored score-descending, so taking the
    first ``degree`` columns is the best-edge prefix of a
    larger-degree build.
    """
    safe = jnp.clip(ids, 0, index.n_docs - 1)
    nbrs = jnp.take(index.knn_ids, safe, axis=0,
                    mode="clip")[..., :degree]          # [Q, k, deg]
    nbrs = jnp.where(ids[..., None] >= 0, nbrs, index.n_docs)
    qn = ids.shape[0]
    return nbrs.reshape(qn, -1).astype(jnp.int32)


def scored_init(ids: jax.Array, n_docs: int) -> jax.Array:
    """The seen-set seed for round 0: the original merge's ids with
    padding mapped to the sentinel."""
    return jnp.where(ids >= 0, ids, n_docs)


def refine_one_round(index: SeismicIndex, q_dense: jax.Array,
                     scores: jax.Array, ids: jax.Array, ev: jax.Array,
                     scored: jax.Array, p: SearchParams, q=None
                     ) -> tuple[jax.Array, jax.Array, jax.Array,
                                jax.Array]:
    """ONE expand + rescore + re-merge round.

    ``scored`` is every id scored in any earlier round (or the
    original merge), sentinel-padded; the round masks it out of the
    expansion so only the genuinely new frontier pays scoring work,
    and returns it widened by this round's candidates. Factored out of
    :func:`refine_batch` so the staged/traced pipeline can run (and
    time) rounds individually — same ops, bit-exact either way.
    """
    from repro.retrieval.merge import merge_topk
    from repro.retrieval.scorer import dedupe_batch, score_candidates
    if p.fuse_level >= 2:
        # one launch: expand + dedupe + seen-mask + compact +
        # rescore — the [Q, k*degree] expansion never leaves VMEM
        from repro.kernels.refine_fused import refine_round_batch
        cand, new_s = refine_round_batch(
            ids, scored, q_dense, index.knn_ids, index.fwd.coords,
            index.fwd.vals, index.fwd_scale, index.fwd_zero,
            n_docs=index.n_docs, degree=p.graph_degree)
    else:
        from repro.retrieval.scorer import compact_candidates
        cand = dedupe_batch(
            expand_neighbors(index, ids, p.graph_degree), index.n_docs)
        seen = (cand[:, :, None] == scored[:, None, :]).any(-1)
        cand = jnp.where(seen, index.n_docs, cand)
        if p.fuse_level >= 1:
            cand = compact_candidates(cand)
        new_s = score_candidates(index, q_dense, cand, p.use_kernel,
                                 fuse_level=p.fuse_level, q=q)
    if index.tombstone is not None:
        # stale graph edges may still point at deleted docs between
        # compactions (and, post-compaction, reverse edges toward a
        # purged id are rewritten lazily) — mask AFTER scoring so both
        # the fused-kernel and unfused paths are covered
        from repro.retrieval.router import NEG
        from repro.retrieval.scorer import mask_tombstoned
        cand = mask_tombstoned(index, cand)
        new_s = jnp.where(cand < index.n_docs, new_s, NEG)
    all_ids = jnp.concatenate(
        [jnp.where(ids >= 0, ids, index.n_docs), cand], axis=1)
    all_s = jnp.concatenate([scores, new_s], axis=1)
    ev = ev + (cand < index.n_docs).sum(axis=-1)
    scores, ids, _ = merge_topk(all_ids, all_s, p.k, index.n_docs)
    return scores, ids, ev, jnp.concatenate([scored, cand], axis=1)


def refine_batch(index: SeismicIndex, q_dense: jax.Array,
                 scores: jax.Array, ids: jax.Array, ev: jax.Array,
                 p: SearchParams, q=None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Neighbor-expand + rescore + re-merge the merged top-k.

    Input/output contract matches ``merge_topk``: (scores [Q, k],
    ids [Q, k] with -1 padding, docs_evaluated [Q]). Traceable; with
    ``refine_rounds == 0`` or ``graph_degree == 0`` it is the
    identity (no ops traced).
    """
    if p.refine_rounds <= 0 or p.graph_degree <= 0:
        return scores, ids, ev
    validate_refine_params(index, p)
    # every id scored in any earlier round (or the original merge):
    # masked out of each round's expansion, so only the genuinely new
    # frontier is rescored and ev counts distinct documents. Grows by
    # k * graph_degree per round — the rounds loop is unrolled, so the
    # widening shape stays static under jit.
    scored = scored_init(ids, index.n_docs)
    for _ in range(p.refine_rounds):
        scores, ids, ev, scored = refine_one_round(
            index, q_dense, scores, ids, ev, scored, p, q)
    return scores, ids, ev
