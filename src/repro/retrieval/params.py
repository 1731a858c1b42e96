"""Query-time hyper-parameters (paper's cut, heap_factor).

``SearchParams`` is a frozen (hashable) dataclass so it can ride as a
static jit argument; every pipeline stage shape is determined by it.

Rather than hand-picking the coupled quality knobs per collection,
indexes tuned with ``repro.tune`` carry persisted ``TunedPolicy``
operating points; ``SearchParams.from_tuned(index, target)`` resolves
the cheapest one meeting a recall target back into pipeline params.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Query-time hyper-parameters shared by every pipeline stage."""

    k: int = 10
    cut: int = 8                  # probed query coordinates
    block_budget: int = 32        # max fully-evaluated blocks
    heap_factor: float = 0.9      # summary over-estimate correction
    policy: str = "adaptive"      # selector registry key ("budget" |
    #                               "adaptive" | "global_threshold" | ...)
    probe_budget: int = 8         # stage-1 blocks for the adaptive policy
    threshold_factor: float = 0.75  # global_threshold: keep blocks with
    #                                 summary >= factor * per-query max
    use_kernel: bool | None = None  # query-weight lookup of the unfused
    #                                 stages: True = the Pallas pair-
    #                                 match kernels, False = the XLA
    #                                 gather, None = by backend (kernels
    #                                 on TPU; kernels.runtime.
    #                                 default_use_kernel)
    fuse_level: int = 0           # kernel-fusion ladder (execution detail,
    #                               results identical at every level):
    #                               0 = unfused reference path (bit-exact
    #                                   with the pre-fusion pipeline);
    #                               1 = candidate compaction — scorer and
    #                                   refine pack live candidates to a
    #                                   prefix and score through the
    #                                   candidate-driven gather_dot kernel
    #                                   (in-kernel forward gather, all-
    #                                   sentinel tiles skipped);
    #                               2 = level 1 + fused router (stage A +
    #                                   top-M + child gather + stage B in
    #                                   one launch) and fused refine
    #                                   (expand + dedupe + rescore in one
    #                                   launch). Fused stages are Pallas-
    #                                   only (interpret off-TPU);
    #                                   `use_kernel` still governs the
    #                                   unfused stages.
    superblock_fanout: int = 0    # hierarchical routing: 0 = flat (score
    #                               every block summary); > 0 = two-stage
    #                               BMP-style route over the coarse
    #                               superblock tier (must match the
    #                               index's SeismicConfig.superblock_fanout)
    superblock_budget: int = 16   # hierarchical routing: superblocks kept
    #                               per query after the coarse stage; only
    #                               their children's block summaries are
    #                               scored (work = cut * n_superblocks +
    #                               superblock_budget * fanout)
    graph_degree: int = 0         # kNN-graph refinement: neighbors expanded
    #                               per merged top-k doc (<= the built
    #                               graph degree; 0 = refine stage is a
    #                               bit-exact no-op)
    refine_rounds: int = 0        # kNN-graph refinement: frontier
    #                               expansions per query (each round
    #                               expands + rescores + re-merges;
    #                               0 = refine stage is a bit-exact no-op)

    def __post_init__(self):
        if self.fuse_level not in (0, 1, 2):
            raise ValueError(
                f"fuse_level must be 0, 1, or 2, got {self.fuse_level}")

    @classmethod
    def from_tuned(cls, index, target: float, *,
                   use_kernel: bool | None = None,
                   fuse_level: int = 0) -> "SearchParams":
        """Resolve the cheapest ``TunedPolicy`` persisted on ``index``
        whose MEASURED recall meets ``target`` (a policy tuned for 0.90
        that measured 0.95 satisfies a 0.92 request).

        Raises ``ValueError`` when the index carries no policy meeting
        the target — under-delivering recall silently is not an option
        for params derived from a persisted artifact. Duck-typed on the
        policy tuple (no ``repro.tune`` import: this module is a leaf).
        """
        policies = getattr(index, "tuned", ()) or ()
        if not policies:
            raise ValueError(
                "index carries no TunedPolicy; run repro.tune."
                "tune_and_attach (or pass explicit SearchParams)")
        feasible = [t for t in policies if t.satisfies(target)]
        if not feasible:
            best = max(t.measured_recall for t in policies)
            raise ValueError(
                f"no persisted TunedPolicy meets recall target "
                f"{target:.4f} (best measured {best:.4f} over "
                f"{len(policies)} policies); re-tune with a higher "
                "target or widen the tuning grid")
        chosen = min(feasible, key=lambda t: (t.measured_cost,
                                              t.router_cost, t.target))
        return chosen.to_params(use_kernel=use_kernel,
                                fuse_level=fuse_level)
