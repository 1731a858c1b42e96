"""Stage 4 — scorer: forward-index exact scoring (paper phase S).

Gathers the member docs of every selected block for the whole batch,
dedupes candidates per query (sort + neighbor mask), and computes the
exact inner products against the forward index. With the kernels on
(``use_kernel``; ``None``, the default, turns them on on a TPU) the
batched gather_dot Pallas kernel scores all [Q, C] candidates in one
launch; a compact (u8) forward index dequantizes inside the kernel.

With ``fuse_level >= 1`` two things change (bit-exact results,
different execution):

* candidates are COMPACTED after dedupe — a second sort packs the live
  ids into a sorted prefix and the duplicate/dead sentinels into the
  tail (:func:`compact_candidates`);
* scoring switches to the candidate-driven kernel
  (:func:`repro.kernels.gather_dot.ops.gather_dot_cand_batch`): the
  forward gather happens inside the kernel (no host-side [Q, C, nnz]
  intermediate) and all-sentinel candidate tiles are skipped entirely,
  so scored work shrinks with the dedupe rate instead of being paid on
  every padded slot.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.kernels.runtime import default_use_kernel
from repro.retrieval.router import NEG, RoutedBatch
from repro.retrieval.selector import Selection
from repro.sparse.ops import PaddedSparse
from repro.sparse.quant import dequantize_u8

if TYPE_CHECKING:  # annotation-only: keeps repro.retrieval import-cycle-free
    from repro.core.types import SeismicIndex


def gather_block_docs(index: SeismicIndex, lists: jax.Array,
                      blocks: jax.Array) -> jax.Array:
    """Member doc ids of selected flat blocks -> [Q, B, block_cap].

    ``blocks`` indexes the flattened (cut, n_blocks) axis of the router
    output; out-of-length slots pad with the sentinel ``n_docs``.
    """
    nb = index.config.n_blocks
    li = blocks // nb                               # [Q, B] probed-slot id
    bi = blocks % nb
    coord = jnp.take_along_axis(lists, li, axis=1)  # [Q, B] coordinate
    off = index.block_off[coord, bi]                # [Q, B]
    ln = index.block_len[coord, bi]
    ar = jnp.arange(index.config.block_cap)
    pos = jnp.clip(off[..., None] + ar, 0, index.config.lam - 1)
    docs = jnp.take_along_axis(index.list_docs[coord], pos, axis=2)
    return jnp.where(ar < ln[..., None], docs, index.n_docs)


def mask_tombstoned(index: SeismicIndex, cand: jax.Array) -> jax.Array:
    """Deleted candidates -> sentinel (identity when the index carries
    no tombstones — the trace-time gate keeps immutable-index programs
    byte-identical).

    Masking at the ID level (not the score level) keeps
    ``docs_evaluated`` consistent with a fresh build of the equivalent
    corpus: a deleted doc is not a candidate at all, rather than a
    candidate with a -inf score.
    """
    if index.tombstone is None:
        return cand
    dead = jnp.take(index.tombstone, cand, mode="clip")
    return jnp.where(dead, index.n_docs, cand)


def score_tail(index: SeismicIndex, q_dense: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Exact scores for the unblocked tail segment -> ([Q, T], [Q, T]).

    Tail docs (``index.tail_ids``) bypass routing/selection entirely:
    they are appended to every query's candidate set and scored through
    the same forward plane as blocked candidates. Zero-score tail docs
    (no coordinate overlap with the query) are masked back to the
    sentinel — a fresh build would never have surfaced them as
    candidates, so both the merge and ``docs_evaluated`` stay
    bit-consistent with the equivalent immutable index.

    Tail ids are always larger than every blocked doc id (ids are
    assigned monotonically and the tail drains at compaction), so
    appending the tail after the deduped block candidates preserves
    the ascending live-candidate order ``merge_topk`` tie-breaking
    relies on. Tail/block candidate sets are disjoint by construction
    (a doc is either compacted into blocks or still in the tail), so
    no cross-segment dedupe is needed.
    """
    tail = mask_tombstoned(index, index.tail_ids)            # [T]
    cand = jnp.broadcast_to(tail[None, :],
                            (q_dense.shape[0], tail.shape[0]))
    scores = score_candidates(index, q_dense, cand, use_kernel=False)
    live = (cand < index.n_docs) & (scores > 0)
    return jnp.where(live, cand, index.n_docs), \
        jnp.where(live, scores, NEG)


def dedupe_batch(cand: jax.Array, n_docs: int) -> jax.Array:
    """Sort each query's candidate ids and mask duplicates to the
    sentinel. [Q, C] -> [Q, C]."""
    s = jnp.sort(cand, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((cand.shape[0], 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
    return jnp.where(dup, n_docs, s)


def compact_candidates(cand: jax.Array) -> jax.Array:
    """Pack live candidate ids into a sorted prefix, sentinels into the
    tail. [Q, C] -> [Q, C].

    After :func:`dedupe_batch` the live ids are ascending but the
    duplicate sentinels sit interspersed among them; one more sort
    moves every sentinel (== n_docs, larger than any live id) to the
    tail while PRESERVING the relative order of the live ids — both
    orders are ascending, so downstream ``merge_topk`` tie-breaking
    (first occurrence wins) is unchanged and results stay bit-exact.
    The payoff is the candidate-driven kernel's tile skip: live work
    concentrates in the leading tiles and the sentinel tail is never
    gathered or scored.
    """
    return jnp.sort(cand, axis=-1)


def score_candidates(index: SeismicIndex, q_dense: jax.Array,
                     cand: jax.Array, use_kernel: bool | None, *,
                     fuse_level: int = 0,
                     q: PaddedSparse | None = None) -> jax.Array:
    """Exact <q, doc> for candidate ids [Q, C] (sentinel -> -inf).

    With a compact (fwd_quant) index the per-doc u8 dequant fuses into
    the gather-dot; scores stay 'exact' up to ~0.4% value quantization.
    At ``fuse_level >= 1`` the candidate-driven kernel gathers forward
    rows in-kernel and skips all-sentinel tiles (see module docstring);
    ``use_kernel`` governs only the unfused path, which scores from
    the padded-sparse query ``q`` when given (the dense rows otherwise).
    """
    if fuse_level >= 1:
        from repro.kernels.gather_dot.ops import gather_dot_cand_batch
        return gather_dot_cand_batch(
            q_dense, cand, index.fwd.coords, index.fwd.vals,
            index.fwd_scale, index.fwd_zero, n_docs=index.n_docs)
    c = jnp.take(index.fwd.coords, cand, axis=0,
                 mode="clip").astype(jnp.int32)              # [Q, C, nnz]
    v = jnp.take(index.fwd.vals, cand, axis=0, mode="clip")
    quant = index.fwd_scale is not None
    scale = zero = None
    if quant:
        scale = jnp.take(index.fwd_scale, cand, mode="clip")
        zero = jnp.take(index.fwd_zero, cand, mode="clip")
    if default_use_kernel(use_kernel):
        from repro.kernels.gather_dot.ops import gather_dot_batch
        scores = gather_dot_batch(q_dense if q is None else q, c, v,
                                  scale, zero)
    else:
        if quant:
            v = dequantize_u8(v, scale, zero)
        else:
            v = v.astype(jnp.float32)
        qn = cand.shape[0]
        gathered = jnp.take_along_axis(
            q_dense, c.reshape(qn, -1), axis=1).reshape(c.shape)
        scores = (gathered * v).sum(axis=-1)
    return jnp.where(cand < index.n_docs, scores, NEG)


def score_selection(index: SeismicIndex, batch: RoutedBatch,
                    sel: Selection, use_kernel: bool | None, *,
                    fuse_level: int = 0) -> tuple[jax.Array, jax.Array]:
    """Selected blocks -> (cand [Q, B*cap], exact scores [Q, B*cap]).

    Blocks carrying a -inf selection score (dead / pruned / already
    evaluated) contribute only sentinel candidates. ``fuse_level >= 1``
    compacts the deduped candidates before the (candidate-driven)
    kernel scores them — bit-exact, see module docstring.

    On a mutable index (``repro.core.mutate``) two extra columns of
    work appear: tombstoned candidates are masked to the sentinel
    before dedupe, and the exactly-scored tail segment is appended
    after the blocked candidates (:func:`score_tail`).
    """
    docs = gather_block_docs(index, batch.lists, sel.blocks)
    docs = jnp.where(jnp.isfinite(sel.block_scores)[..., None], docs,
                     index.n_docs)
    qn = docs.shape[0]
    cand = dedupe_batch(mask_tombstoned(index, docs.reshape(qn, -1)),
                        index.n_docs)
    if fuse_level >= 1:
        cand = compact_candidates(cand)
    scores = score_candidates(index, batch.q_dense, cand, use_kernel,
                              fuse_level=fuse_level, q=batch.q)
    if index.tail_ids is not None:
        tail_cand, tail_scores = score_tail(index, batch.q_dense)
        cand = jnp.concatenate([cand, tail_cand], axis=1)
        scores = jnp.concatenate([scores, tail_scores], axis=1)
    return cand, scores
