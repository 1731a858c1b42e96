"""Observability overhead gate: full instrumentation — tracing AND
shadow-oracle quality auditing at its default cadence — must stay
within <5% p50 request latency and <3% QPS of the uninstrumented
server.

Two measurements, one gate:

**Component measurement (the gate).** Times the instrumentation code
paths themselves, on the real index and launch shapes:

  * per-request span work — ``start_trace`` + ``queue_wait``/``launch``
    span assembly + ``end_trace``, exactly the calls the batcher makes
    per request (all on the request's critical path);
  * the staged-launch delta — ``run_pipeline_staged`` (with span
    collection, the full sampled path) minus the fused
    ``search_pipeline``, amortized by the
    default ``stage_sample_every`` since only every Nth launch pays it;
  * the audit hot-path cost — ``ShadowAuditor.plan`` runs on every
    launch; ``feed`` (row copies + a bounded, non-blocking enqueue)
    plus the forced staged launch only every ``audit_sample_every``-th
    request. The oracle recompute itself runs on the background worker
    thread, off the request path, so it is deliberately not gated.

  p50 overhead  = (span_work + audit_plan) / baseline_p50
  QPS overhead  = (span_work + audit_plan
                   + staged_delta / sample_every
                   + (audit_feed + staged_delta) / audit_every)
                  / baseline_mean

**Interleaved A/B (informational rows).** Closed-loop traffic against
a bare and an instrumented server in alternating segments. On a shared
CI box, per-run thread placement alone moves wall-clock QPS by ±5% —
more than the true overhead — so the A/B rows document the end-to-end
picture while the deterministic component measurement carries the
gate; sub-noise gating on wall clock would only measure the host.

    PYTHONPATH=src python -m benchmarks.obs_overhead [--smoke]

Exits nonzero when a gate fails (CI runs ``--smoke``; ``make
bench-obs`` runs it too).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.core import SeismicConfig, build_index
from repro.data import SyntheticSparseConfig, make_collection
from repro.obs import Observability, ShadowAuditor, Tracer
from repro.obs.registry import MetricsRegistry
from repro.retrieval import SearchParams, search_pipeline
from repro.retrieval.pipeline import run_pipeline_staged, stage_fns
from repro.serve import AsyncSeismicServer
from repro.serve.batcher import attach_stage_spans
from repro.sparse.ops import PaddedSparse

P50_GATE_PCT = 5.0    # p50 request latency overhead must stay below
QPS_GATE_PCT = 3.0    # QPS loss must stay below

# Sized so one request is ms-scale pipeline work — the scale the
# serving path is for. On sub-ms toy requests every comparison
# measures thread-scheduling jitter, not instrumentation.
FIXTURE = SyntheticSparseConfig(dim=1024, n_docs=8192, n_queries=32,
                                doc_nnz=64, query_nnz=24, n_topics=32,
                                topic_coords=128, seed=5)
FIXTURE_INDEX = SeismicConfig(lam=128, beta=8, alpha=0.4, block_cap=32,
                              summary_nnz=32)


def _fixture():
    docs_np, queries_np, _ = make_collection(FIXTURE)
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), docs_np.dim)
    queries = PaddedSparse(jnp.asarray(queries_np.coords),
                           jnp.asarray(queries_np.vals), queries_np.dim)
    return build_index(docs, FIXTURE_INDEX, list_chunk=16), queries


def _span_work_us(iters: int = 2000) -> float:
    """Per-request tracer cost: the exact span calls the batcher makes
    for one served request (submit mint + queue/launch spans + close)."""
    tracer = Tracer(capacity=256)
    t0 = time.perf_counter()
    for _ in range(iters):
        tr = tracer.start_trace("request", 0.0)
        tracer.add_span(tr, "queue_wait", 0.0, 1.0)
        sp = tracer.add_span(tr, "launch", 1.0, 2.0, width=8,
                             occupancy=1, batch_seq=0, staged=False)
        tracer.end_trace(tr, 2.0, status="done", docs_evaluated=0)
        del sp
    return (time.perf_counter() - t0) / iters * 1e6


def _audit_cost_us(idx, p, nnz: int, k: int,
                   iters: int = 2000) -> tuple[float, float, int]:
    """Hot-path cost of the shadow auditor: per-launch ``plan`` and
    per-sampled-request ``feed`` (row copies + ``put_nowait``). Uses an
    unstarted auditor with a queue sized past ``iters`` so the oracle
    worker never runs — only the request-path code is on the clock."""
    aud = ShadowAuditor(idx, p, MetricsRegistry(),
                        queue_bound=iters + 8)
    t0 = time.perf_counter()
    for _ in range(iters):
        aud.plan(8)
    plan_us = (time.perf_counter() - t0) / iters * 1e6
    coords = np.zeros(nnz, np.int32)
    vals = np.zeros(nnz, np.float32)
    ids = np.zeros(k, np.int32)
    t0 = time.perf_counter()
    for _ in range(iters):
        aud.feed(coords, vals, ids, captures=None)
    feed_us = (time.perf_counter() - t0) / iters * 1e6
    return plan_us, feed_us, aud.audit_sample_every


def _launch_us(fn, iters: int = 12) -> float:
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _staged_delta_us(idx, p, width: int, nnz: int) -> float:
    """Extra wall time of one fully-instrumented staged launch (span
    collection + stage-span assembly) over the fused launch it
    replaces."""
    coords = jnp.zeros((width, nnz), jnp.int32)
    vals = jnp.zeros((width, nnz), jnp.float32)
    q = PaddedSparse(coords, vals, idx.dim)
    fns = stage_fns(idx, p)
    tracer = Tracer()

    def staged():
        triples, probed = [], {}
        out = run_pipeline_staged(
            idx, coords, vals, p, fns=fns,
            span_cb=lambda name, a, b: triples.append((name, a, b)),
            split_refine=True, probe=probed.__setitem__)
        tr = tracer.start_trace("launch", 0.0)
        attach_stage_spans(tracer, tr, tr.root, triples)
        tracer.end_trace(tr, 1.0)
        return out

    def fused():
        return search_pipeline(idx, q, p)

    jax.block_until_ready(staged())
    jax.block_until_ready(fused())
    return max(0.0, _launch_us(staged) - _launch_us(fused))


def _segment(server, coords, vals, n_req: int,
             lat: list, t_total: list) -> None:
    """One closed-loop segment: append per-request latencies and the
    segment's wall time to the arm's running pools."""
    qn = coords.shape[0]
    t0 = time.perf_counter()
    for i in range(n_req):
        t = time.perf_counter()
        server.submit(coords[i % qn], vals[i % qn]).result(timeout=60)
        lat.append(time.perf_counter() - t)
    t_total.append(time.perf_counter() - t0)


def _ab_wallclock(idx, queries, p, n_req: int, segments: int,
                  obs) -> dict:
    """Interleaved closed-loop A/B (informational; see module doc)."""
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)

    def make(o):
        return AsyncSeismicServer(
            idx, p, max_batch=8, query_nnz=int(coords.shape[1]),
            deadline_s=1e-4, cache_size=0, coalesce=False, obs=o)

    lat = {"off": [], "on": []}
    t_total = {"off": [], "on": []}
    with make(None) as off, make(obs) as on:
        _segment(off, coords, vals, n_req, [], [])     # warm both arms
        _segment(on, coords, vals, n_req, [], [])
        for s in range(segments):
            order = (("off", off), ("on", on)) if s % 2 == 0 \
                else (("on", on), ("off", off))
            for arm, server in order:
                _segment(server, coords, vals, n_req,
                         lat[arm], t_total[arm])
    return {arm: {"qps": segments * n_req / sum(t_total[arm]),
                  "p50": float(np.percentile(lat[arm], 50)),
                  "mean": float(np.mean(lat[arm]))}
            for arm in ("off", "on")}


def _write_trail(obs, artifacts_dir) -> None:
    """Persist the instrumented arm's metric snapshot and Chrome trace
    export next to the BENCH_*.json artifacts — the inputs
    ``python -m repro.obs.report`` renders."""
    import json
    import pathlib

    from repro.obs import write_jsonl_snapshot
    d = pathlib.Path(artifacts_dir)
    write_jsonl_snapshot(obs.registry, str(d / "obs_snapshots.jsonl"),
                         extra={"bench": "obs_overhead"})
    (d / "obs_traces.json").write_text(
        json.dumps(obs.tracer.export_chrome()))


def run(smoke: bool = False, artifacts_dir=None):
    idx, queries = _fixture()
    p = SearchParams(k=10, cut=8, block_budget=16, policy="adaptive")
    n_req, segments = (16, 4) if smoke else (16, 12)

    obs = Observability.create()
    # The instrumented arm carries the full quality plane too: a
    # started shadow auditor at its default cadence rides the A/B.
    obs.auditor = ShadowAuditor(idx, p, obs.registry)
    with obs.auditor:
        ab = _ab_wallclock(idx, queries, p, n_req, segments, obs)
        obs.auditor.drain()
    if artifacts_dir is not None:
        # the instrumented arm's obs trail, for `repro.obs.report`
        _write_trail(obs, artifacts_dir)
    span_us = _span_work_us()
    sample_every = obs.stage_sample_every
    staged_us = _staged_delta_us(idx, p, width=8,
                                 nnz=int(queries.coords.shape[1]))
    plan_us, feed_us, audit_every = _audit_cost_us(
        idx, p, nnz=int(queries.coords.shape[1]), k=p.k)
    base_p50_us = ab["off"]["p50"] * 1e6
    base_mean_us = ab["off"]["mean"] * 1e6
    p50_pct = (span_us + plan_us) / base_p50_us * 100
    qps_pct = (span_us + plan_us + staged_us / sample_every
               + (feed_us + staged_us) / audit_every) \
        / base_mean_us * 100

    for arm in ("off", "on"):
        yield row(f"obs_overhead_{arm}", 1e6 / ab[arm]["qps"],
                  qps=f"{ab[arm]['qps']:.3g}",
                  p50_ms=f"{ab[arm]['p50'] * 1e3:.2f}")
    yield row("obs_overhead_gate", 0.0,
              span_work_us=f"{span_us:.1f}",
              staged_delta_us=f"{staged_us:.0f}",
              sample_every=sample_every,
              audit_plan_us=f"{plan_us:.2f}",
              audit_feed_us=f"{feed_us:.2f}",
              audit_every=audit_every,
              p50_overhead_pct=f"{p50_pct:.2f}",
              qps_loss_pct=f"{qps_pct:.2f}",
              gate_p50=p50_pct < P50_GATE_PCT,
              gate_qps=qps_pct < QPS_GATE_PCT)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer requests / segments (CI smoke)")
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="also write the obs snapshot/trace trail here")
    args = ap.parse_args()
    if args.artifacts:
        import pathlib
        pathlib.Path(args.artifacts).mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    failed = False
    for line in run(smoke=args.smoke, artifacts_dir=args.artifacts):
        print(line)
        if "gate_" in line and "=False" in line:
            failed = True
    if failed:
        raise SystemExit("obs overhead gate FAILED")
