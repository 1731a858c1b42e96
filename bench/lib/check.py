"""What decides ``correct``: the answers the timed path served, set
against the plain reference (``reference.py``) on the same data.

Four numbers, each with its limit:

* ``unanswered`` — requests attempted in the window that never got an
  answer (rejected, failed, or not back a minute after the close).
  Limit 0: the configuration guarantees every request an answer.
* ``bad_answers`` — answers holding an id outside the corpus, an id
  twice, or scores out of descending order. Limit 0.
* ``recall_at_10`` — mean share of each answer's ids that are among its
  query's exact top-k. Limit: the recall target the configuration
  states.
* ``score_gap`` — the widest gap between a served score and the
  reference's score of the same (query, document), as a share of the
  query's best reference score. Covers the scorer; a wrong id also
  shows here, as its reference score is not the one served. Limit: set
  in the configuration from readings of the program and of its
  lower-precision control (PERF.md gives both).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from lib import reference


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    rule: str                  # "<=" or ">="

    @property
    def ok(self) -> bool:
        if self.rule == "<=":
            return bool(self.value <= self.limit)
        return bool(self.value >= self.limit)

    def line(self) -> str:
        return (f"check {self.name} {self.value!r} {self.rule} "
                f"{self.limit!r} {'ok' if self.ok else 'FAILED'}")


def bad_rows(ids: np.ndarray, scores: np.ndarray, n_docs: int) -> np.ndarray:
    """Rows with an id outside [0, n_docs), a repeated id, or scores
    that are not in descending order."""
    out_of_range = ((ids < 0) | (ids >= n_docs)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    misordered = (np.diff(scores, axis=1) > 0).any(axis=1)
    return out_of_range | repeated | misordered


def recall(ids: np.ndarray, qidx: np.ndarray,
           exact_ids: np.ndarray) -> np.ndarray:
    """Per answer: |served ids ∩ exact top-k of its query| / k."""
    ex = exact_ids[qidx]
    hit = (ids[:, :, None] == ex[:, None, :]).any(axis=2)
    return hit.sum(axis=1) / exact_ids.shape[1]


def score_gap(ids, scores, qidx, exact_scores, q_dense, doc_coords,
              doc_vals) -> float:
    """Widest |served − reference| over the query's best reference
    score, across every in-range (answer, slot). Each distinct (query,
    document) pair is scored once by the reference."""
    n_docs = doc_coords.shape[0]
    valid = (ids >= 0) & (ids < n_docs)
    q = np.broadcast_to(qidx[:, None], ids.shape)[valid]
    d = ids[valid].astype(np.int64)
    if d.size == 0:
        return 0.0
    key, inv = np.unique(q * n_docs + d, return_inverse=True)
    ref = reference.pair_scores(q_dense, doc_coords, doc_vals,
                                key // n_docs, key % n_docs)[inv]
    top = exact_scores[q, 0].astype(np.float64)
    gap = np.abs(scores[valid].astype(np.float64) - ref) / np.maximum(
        top, np.finfo(np.float32).tiny)
    return float(gap.max())


def compare(*, ids, scores, qidx, answered, exact_ids, exact_scores,
            q_dense, doc_coords, doc_vals, recall_target: float,
            score_gap_limit: float) -> list[Check]:
    """The checks of one run. ``ids``/``scores``/``qidx`` hold every
    attempted request; ``answered`` marks those that came back."""
    n_docs = doc_coords.shape[0]
    ids, scores, qidx = ids[answered], scores[answered], qidx[answered]
    rec = float(recall(ids, qidx, exact_ids).mean()) if ids.size else 0.0
    return [
        Check("unanswered", float((~answered).sum()), 0.0, "<="),
        Check("bad_answers", float(bad_rows(ids, scores, n_docs).sum()),
              0.0, "<="),
        Check("recall_at_10", rec, recall_target, ">="),
        Check("score_gap", score_gap(ids, scores, qidx, exact_scores,
                                     q_dense, doc_coords, doc_vals),
              score_gap_limit, "<="),
    ]
