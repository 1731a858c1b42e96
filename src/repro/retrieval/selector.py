"""Stage 3 — selector: pluggable block-selection policies.

The policy that decides WHICH routed blocks get exact scoring is the
decisive accuracy/cost lever of block-based sparse retrieval (Seismic
Alg. 2; Block-Max Pruning, Mallia et al. 2024; Bruch et al. 2023), so
it is a registry of batch-first functions rather than branches inside
the pipeline. A selector maps the routed batch to a fixed-shape block
selection:

    fn(index, batch: RoutedBatch, p: SearchParams) -> Selection

Blocks it wants ignored keep a -inf score; the scorer masks their docs
to the sentinel. ``SearchParams.policy`` picks the registry entry, so
new policies apply to local, served, and distributed search alike.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.retrieval.params import SearchParams
from repro.retrieval.router import NEG, RoutedBatch

if TYPE_CHECKING:  # annotation-only: keeps repro.retrieval import-cycle-free
    from repro.core.types import SeismicIndex


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Selection:
    """Fixed-shape batched block selection."""

    blocks: jax.Array        # i32 [Q, B] flat ids into RoutedBatch.r
    block_scores: jax.Array  # f32 [Q, B] summary scores (-inf = masked)


SelectorFn = Callable[["SeismicIndex", RoutedBatch, SearchParams], Selection]

_SELECTORS: dict[str, SelectorFn] = {}


def register_selector(name: str, fn: SelectorFn | None = None):
    """Register a block-selection policy (usable as a decorator)."""
    def wrap(f: SelectorFn) -> SelectorFn:
        _SELECTORS[name] = f
        return f
    return wrap if fn is None else wrap(fn)


def get_selector(name: str) -> SelectorFn:
    try:
        return _SELECTORS[name]
    except KeyError:
        raise KeyError(f"unknown selector policy {name!r}; "
                       f"registered: {sorted(_SELECTORS)}") from None


def selector_names() -> tuple[str, ...]:
    return tuple(sorted(_SELECTORS))


@register_selector("budget")
def select_budget(index: SeismicIndex, batch: RoutedBatch,
                  p: SearchParams) -> Selection:
    """Top ``block_budget`` blocks by summary score (IVF-style routing,
    one pass)."""
    scores, blocks = jax.lax.top_k(batch.r, p.block_budget)
    return Selection(blocks=blocks, block_scores=scores)


@register_selector("global_threshold")
def select_global_threshold(index: SeismicIndex, batch: RoutedBatch,
                            p: SearchParams) -> Selection:
    """BMP-style global threshold: keep blocks whose summary score
    clears ``threshold_factor`` of the per-query best block (the
    block-max upper bound), capped at ``block_budget``. One routing
    pass, no forward-index bootstrap."""
    rmax = jnp.max(batch.r, axis=-1, keepdims=True)         # [Q, 1]
    passing = batch.r >= rmax * p.threshold_factor
    kept = jnp.where(passing, batch.r, NEG)
    scores, blocks = jax.lax.top_k(kept, p.block_budget)
    return Selection(blocks=blocks, block_scores=scores)


@register_selector("adaptive")
def select_adaptive(index: SeismicIndex, batch: RoutedBatch,
                    p: SearchParams) -> Selection:
    """Two-stage emulation of Alg. 2's heap_factor pruning: stage 1
    fully scores the top ``probe_budget`` blocks to bootstrap a
    k-th-best estimate theta; stage 2 keeps only blocks with
    summary >= theta / heap_factor (capped at block_budget). Recovers
    the paper's dynamic pruning without a serial heap."""
    from repro.retrieval.scorer import (compact_candidates, dedupe_batch,
                                        gather_block_docs, mask_tombstoned,
                                        score_candidates)
    # ---- stage 1: bootstrap theta from the top probe_budget blocks
    # (clamped: a block_budget below probe_budget degrades to pure
    # budget routing instead of a negative stage-2 top_k)
    probe = min(p.probe_budget, p.block_budget)
    r1, b1 = jax.lax.top_k(batch.r, probe)
    qn = batch.r.shape[0]
    cand1 = gather_block_docs(index, batch.lists, b1).reshape(qn, -1)
    # deleted docs must not inflate theta: a tombstoned high scorer
    # would tighten the stage-2 threshold against docs that can never
    # be returned (tail docs are not folded in — theta only ever ends
    # up lower, which keeps MORE blocks, never fewer)
    cand1 = dedupe_batch(mask_tombstoned(index, cand1), index.n_docs)
    if p.fuse_level >= 1:
        cand1 = compact_candidates(cand1)
    s1 = score_candidates(index, batch.q_dense, cand1, p.use_kernel,
                          fuse_level=p.fuse_level, q=batch.q)
    theta = jax.lax.top_k(s1, p.k)[0][:, -1]                # [Q]
    theta = jnp.where(jnp.isfinite(theta), theta, NEG)
    # ---- stage 2: Alg. 2 line 6 -> keep blocks w/ r >= theta/heap_factor
    rows = jnp.arange(qn)[:, None]
    r2 = batch.r.at[rows, b1].set(NEG)                      # already done
    passing = r2 >= theta[:, None] / p.heap_factor
    r2 = jnp.where(passing, r2, NEG)
    v2, b2 = jax.lax.top_k(r2, p.block_budget - probe)
    return Selection(blocks=jnp.concatenate([b1, b2], axis=1),
                     block_scores=jnp.concatenate([r1, v2], axis=1))
