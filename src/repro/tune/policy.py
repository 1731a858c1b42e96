"""``TunedPolicy`` — a persisted recall-target operating point.

The quality/cost trade-off of the staged pipeline is governed by a
COUPLED knob set (``block_budget`` x ``policy`` factors x superblock
budget x ``refine_rounds``): halving ``block_budget`` loses recall that
one refine round often buys back at a fraction of the scoring work, so
the knobs only make sense tuned together, per collection, against a
recall target (paper §5 tunes them by hand; Mallia et al. 2024 and
Bruch et al. 2023 show the selection-policy + budget pair is the
decisive lever). ``repro.tune`` turns the hand-tuned constants into a
first-class index artifact:

  * ``TunedPolicy`` is the frozen, JSON-round-trippable record of one
    tuned operating point: the recall target it was tuned for, every
    quality knob of ``SearchParams``, the measured recall / cost on the
    held-out sample, and an order-invariant fingerprint of that sample.
  * A ``SeismicIndex`` carries a tuple of them (static metadata, like
    ``config``); ``ckpt.save_index`` persists them in the manifest with
    pre-tune back-compat (old checkpoints load with ``tuned == ()``).
  * ``SearchParams.from_tuned(index, target)`` resolves the cheapest
    persisted policy meeting a target back into pipeline params,
    bit-exactly (every knob is stored, nothing is re-derived).
  * Serving validates the persisted policies against the index at
    construction (:func:`validate_tuned_index`), so a stale policy
    (graph dropped, superblock tier rebuilt with another fanout) fails
    fast instead of at trace time.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from repro.retrieval.params import SearchParams

# SearchParams quality knobs a tuned policy pins (everything except the
# execution details ``use_kernel`` / ``fuse_level``, which the caller
# picks per backend — they never change results)
KNOB_FIELDS = ("k", "cut", "block_budget", "heap_factor", "policy",
               "probe_budget", "threshold_factor", "superblock_fanout",
               "superblock_budget", "graph_degree", "refine_rounds")

# recall comparisons tolerate one float ulp-ish of slack so a policy
# measured exactly AT the target is feasible after a JSON round-trip
RECALL_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class TunedPolicy:
    """One tuned operating point (frozen + hashable: it rides the index
    pytree as static metadata, like ``SeismicConfig``)."""

    target: float                  # recall@k target it was tuned for
    # ---- the coupled knob set (mirrors SearchParams' quality knobs)
    k: int = 10
    cut: int = 8
    block_budget: int = 32
    heap_factor: float = 0.9
    policy: str = "budget"
    probe_budget: int = 8
    threshold_factor: float = 0.75
    superblock_fanout: int = 0
    superblock_budget: int = 16
    graph_degree: int = 0
    refine_rounds: int = 0
    # ---- what the tuner measured on the held-out sample
    measured_recall: float = 0.0   # mean recall@k
    measured_cost: float = 0.0     # mean docs exactly scored per query
    router_cost: int = 0           # summary dots per query (router_work)
    sample_fingerprint: str = ""   # order-invariant sample digest
    modeled: bool = False          # True: config-time model, not measured

    def to_params(self, *, use_kernel: bool | None = None,
                  fuse_level: int = 0) -> SearchParams:
        """The pipeline params this policy pins — bit-exact: every knob
        is stored on the policy, nothing is re-derived. ``use_kernel``
        and ``fuse_level`` are execution details (results identical at
        every level), so the caller picks them per backend."""
        return SearchParams(use_kernel=use_kernel, fuse_level=fuse_level,
                            **{f: getattr(self, f) for f in KNOB_FIELDS})

    def satisfies(self, target: float) -> bool:
        return self.measured_recall >= target - RECALL_EPS


def knobs_from_params(p: SearchParams) -> dict:
    """The persistable quality-knob subset of ``SearchParams``."""
    return {f: getattr(p, f) for f in KNOB_FIELDS}


def sample_fingerprint(coords, vals) -> str:
    """Order-invariant digest of a held-out query sample.

    Per-query row digests are sorted before the final hash, so a
    permuted sample fingerprints identically — the tuner's selection is
    order-invariant (means over queries), and the fingerprint must be
    too, or re-tuning on a shuffled sample would look like a different
    sample.
    """
    c = np.ascontiguousarray(np.asarray(coords))
    v = np.ascontiguousarray(np.asarray(vals, np.float32))
    rows = sorted(
        hashlib.sha256(c[i].tobytes() + v[i].tobytes()).digest()
        for i in range(c.shape[0]))
    return hashlib.sha256(b"".join(rows)).hexdigest()[:16]


def row_digest(coords_row, vals_row) -> bytes:
    """Canonical digest of ONE padded-sparse query row.

    Unlike :func:`sample_fingerprint`'s raw-bytes row hash (kept
    byte-stable for persisted policies), this canonicalizes first —
    dtypes pinned to i32/f32, padding coordinates zeroed, entries
    sorted by (coord, val) — so a query digests identically however
    its nnz entries are ordered or padded. The quality plane's drift
    sketch uses these to test served queries for literal membership in
    the tuning sample.
    """
    c = np.asarray(coords_row, np.int32).reshape(-1)
    v = np.asarray(vals_row, np.float32).reshape(-1)
    c = np.where(v > 0, c, 0)
    v = np.where(v > 0, v, 0.0).astype(np.float32)
    order = np.lexsort((v, c))
    c = np.ascontiguousarray(c[order])
    v = np.ascontiguousarray(v[order])
    return hashlib.sha256(c.tobytes() + v.tobytes()).digest()


def row_digests(coords, vals) -> list[bytes]:
    """Per-row :func:`row_digest` over a [Q, nnz] padded sample."""
    c = np.asarray(coords)
    v = np.asarray(vals)
    return [row_digest(c[i], v[i]) for i in range(c.shape[0])]


def attach_tuned(index, policies) -> "SeismicIndex":  # noqa: F821
    """Return the index carrying ``policies`` (sorted by target then
    cost, so the persisted tuple is deterministic regardless of tuning
    order). Replaces any previously attached policies."""
    pols = tuple(sorted(policies,
                        key=lambda t: (t.target, t.measured_cost,
                                       t.measured_recall)))
    for t in pols:
        validate_policy(index, t)
    return dataclasses.replace(index, tuned=pols)


def validate_policy(index, policy: TunedPolicy) -> None:
    """Fail fast when a (possibly persisted) policy no longer matches
    the index it rides on — the serve-construction check."""
    from repro.graph.refine import validate_refine_params
    from repro.retrieval.selector import selector_names
    if not (0.0 < policy.target <= 1.0):
        raise ValueError(f"TunedPolicy.target must be in (0, 1], got "
                         f"{policy.target}")
    if policy.k < 1 or policy.cut < 1 or policy.block_budget < 1:
        raise ValueError(
            f"TunedPolicy has degenerate knobs: k={policy.k}, "
            f"cut={policy.cut}, block_budget={policy.block_budget}")
    if policy.policy not in selector_names():
        raise ValueError(
            f"TunedPolicy.policy {policy.policy!r} is not a registered "
            f"selector (have {sorted(selector_names())})")
    if policy.superblock_fanout > 0:
        if index.sup_coords is None:
            raise ValueError(
                "TunedPolicy routes hierarchically (superblock_fanout="
                f"{policy.superblock_fanout}) but the index has no "
                "superblock tier")
        if policy.superblock_fanout != index.config.superblock_fanout:
            raise ValueError(
                f"TunedPolicy superblock_fanout={policy.superblock_fanout}"
                f" mismatches the index tier "
                f"({index.config.superblock_fanout})")
    validate_refine_params(index, policy.to_params())


def validate_tuned_index(index) -> None:
    """Validate every policy attached to an index (serve construction:
    a stale persisted policy must fail before the first launch)."""
    for t in getattr(index, "tuned", ()) or ():
        validate_policy(index, t)
