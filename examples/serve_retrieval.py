"""Serving demo: the synchronous SeismicServer facade, the async
deadline micro-batching server, end-to-end observability (request
tracing + a live Prometheus/trace HTTP endpoint), serving a TUNED
operating point resolved from the index, shadow-oracle quality
auditing of live traffic (the /quality.json recall/funnel plane), and
a small LMDecoder generation loop.

Every retrieval launch runs the six-stage pipeline
(prep -> router -> selector -> scorer -> merge -> refine; see
src/repro/retrieval/README.md) — the refine stage traces as the
identity until an index carries a kNN graph and the params enable it.

    PYTHONPATH=src python examples/serve_retrieval.py
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import SeismicConfig, SearchParams, build_index
from repro.core.baselines import exact_search
from repro.core.oracle import recall_at_k
from repro.data import SyntheticSparseConfig, make_collection
from repro.graph import build_doc_graph
from repro.models.api import get_bundle
from repro.serve import AsyncSeismicServer, LMDecoder, SeismicServer
from repro.sparse.ops import PaddedSparse
from repro.tune import tune_and_attach


def build_demo_index():
    cfg = SyntheticSparseConfig(dim=2048, n_docs=8192, n_queries=300,
                                doc_nnz=96, query_nnz=32)
    docs_np, queries_np, _ = make_collection(cfg)
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), docs_np.dim)
    queries = PaddedSparse(jnp.asarray(queries_np.coords),
                           jnp.asarray(queries_np.vals), queries_np.dim)
    index = build_index(docs, SeismicConfig(lam=192, beta=12, alpha=0.4,
                                            block_cap=32, summary_nnz=48),
                        list_chunk=32)
    return docs, queries, index


def retrieval_demo(docs, queries, index):
    print("== SeismicServer: batched approximate retrieval ==")
    server = SeismicServer(index, SearchParams(k=10, cut=10,
                                               block_budget=16,
                                               policy="adaptive"),
                           max_batch=128)
    t0 = time.time()
    result = server.search(queries)   # 300 queries -> 3 padded batches
    dt = time.time() - t0
    _, exact_ids = exact_search(docs, queries, 10)
    rec = np.mean([recall_at_k(result.ids[q], np.asarray(exact_ids[q]))
                   for q in range(queries.n)])
    print(f"   300 queries in {dt*1000:.0f} ms "
          f"({dt/300*1e6:.0f} us/query CPU-JAX)  recall@10={rec:.3f}  "
          f"mean docs evaluated={result.docs_evaluated.mean():.0f}")


def async_demo(queries, index):
    """Submit per-request traffic with dispatch deadlines; print the
    occupancy / latency / cache telemetry the server exports."""
    print("== AsyncSeismicServer: deadline micro-batching ==")
    server = AsyncSeismicServer(
        index, SearchParams(k=10, cut=10, block_budget=16,
                            policy="adaptive"),
        max_batch=32, query_nnz=queries.nnz_max, deadline_s=0.01,
        queue_bound=512, admission="reject", cache_size=512)
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    rng = np.random.default_rng(0)
    n_req = 2 * queries.n                 # every query twice: cache hits
    with server:
        futs = []
        t0 = time.time()
        for i in range(n_req):
            q = i % queries.n
            futs.append(server.submit(coords[q], vals[q],
                                      deadline_s=0.01))
            time.sleep(float(rng.exponential(2e-4)))   # ~5k qps offered
        for f in futs:
            f.wait()
        dt = time.time() - t0
    tel = server.telemetry_export()
    lat = tel["latency_s"]["request_e2e"]
    done = sum(f.status == "done" for f in futs)
    print(f"   {done}/{n_req} requests in {dt*1000:.0f} ms "
          f"({done/dt:.0f} qps)")
    print(f"   launches={tel['batch']['launches']}  "
          f"mean occupancy={tel['batch']['mean_occupancy']:.1f}/32  "
          f"max queue depth={tel['queue']['depth_max']}")
    print(f"   latency p50={lat['p50']*1e3:.1f}ms "
          f"p95={lat['p95']*1e3:.1f}ms p99={lat['p99']*1e3:.1f}ms")
    print(f"   cache hit-rate={tel['cache']['hit_rate']:.2f} "
          f"({tel['cache']['hits']} hits)")


def replica_demo(docs, queries, index):
    """Replica-parallel serving behind the one request queue: a
    mirrored fleet with one deliberately slow replica (the stage-timing
    balancer steers load away from it), then the same corpus split over
    doc shards with per-shard top-k merged under the pad-row mask."""
    from repro.core.distributed import build_sharded_index
    from repro.serve import ReplicaSeismicServer

    print("== ReplicaSeismicServer: replica-parallel serving ==")
    p = SearchParams(k=10, cut=10, block_budget=16, policy="adaptive")
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    server = ReplicaSeismicServer(
        index, p, n_replicas=3, mode="mirror",
        replica_delay_s=[0.012, 0.003, 0.003],   # replica 0 is 4x slower
        max_batch=16, query_nnz=queries.nnz_max, deadline_s=0.004,
        queue_bound=1024, cache_size=0, coalesce=False)
    with server:
        futs = []
        for i in range(240):
            futs.append(server.submit(coords[i % queries.n],
                                      vals[i % queries.n]))
            time.sleep(0.001)
        for f in futs:
            f.wait()
    snap = server.balancer.snapshot()
    print("   mirror x3, replica 0 slowed 4x:")
    print("   dispatch share = "
          + str([round(s, 2) for s in snap["dispatch_share"]])
          + "  cost EWMA ms = "
          + str([round(c * 1e3, 1) for c in snap["cost_ewma_s"]]))

    stacked = build_sharded_index(docs, index.config, n_shards=4,
                                  list_chunk=32)
    sharded = ReplicaSeismicServer(
        stacked, p, mode="shard", max_batch=16,
        query_nnz=queries.nnz_max, deadline_s=0.004, cache_size=0)
    sub = queries[:64]
    with sharded:
        futs = [sharded.submit(coords[i], vals[i]) for i in range(64)]
        ids = np.stack([f.result(30.0).ids for f in futs])
    _, exact_ids = exact_search(docs, sub, 10)
    rec = np.mean([recall_at_k(ids[q], np.asarray(exact_ids[q]))
                   for q in range(64)])
    print(f"   shard x4: 64 queries served over 4 doc shards, "
          f"merged recall@10={rec:.3f}")


def observability_demo(queries, index):
    """Serve traced traffic with a live metrics endpoint: scrape the
    Prometheus exposition over HTTP, print a snapshot table and the
    slowest request span trees, and save one Chrome trace."""
    import json
    import urllib.request

    from repro.obs import Observability, start_exporter
    from repro.obs.report import slowest_traces_table, snapshot_table

    print("== Observability: tracing + metrics endpoint ==")
    obs = Observability.create(stage_sample_every=4)   # demo: lots of detail
    server = AsyncSeismicServer(
        index, SearchParams(k=10, cut=10, block_budget=16,
                            policy="adaptive"),
        max_batch=32, query_nnz=queries.nnz_max, deadline_s=0.005,
        cache_size=128, obs=obs)
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    with server, start_exporter(obs.registry, obs.tracer) as exporter:
        futs = [server.submit(coords[i % queries.n], vals[i % queries.n])
                for i in range(128)]
        for f in futs:
            f.wait()
        with urllib.request.urlopen(exporter.url + "/metrics") as r:
            metrics = r.read().decode()
        with urllib.request.urlopen(exporter.url + "/traces") as r:
            chrome = json.load(r)
    print(f"   scraped {exporter.url}/metrics "
          f"({len(metrics.splitlines())} lines); excerpt:")
    for line in metrics.splitlines():
        if line.startswith(("seismic_cache_hit_rate",
                            "seismic_docs_evaluated_mean",
                            "seismic_events_total{event=\"compilations\"}")):
            print("     " + line)
    print("   -- metric snapshot (excerpt) --")
    snap = {k: v for k, v in obs.registry.snapshot().items()
            if k in ("seismic_latency_seconds", "seismic_events_total")}
    for line in snapshot_table(snap, max_rows=12).splitlines():
        print("     " + line)
    print("   -- slowest traced requests --")
    for line in slowest_traces_table(chrome, n=3).splitlines():
        print("     " + line)
    path = "/tmp/seismic_trace.json"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(chrome, f)
    print(f"   Chrome trace ({len(chrome['traceEvents'])} events) -> "
          f"{path} (load in Perfetto / chrome://tracing)")


def tuned_demo(docs, queries, index):
    """Tune an operating point for a recall target on a held-out query
    sample, persist it ON the index, and serve with params resolved
    from the artifact instead of hand-picked knobs."""
    print("== TunedPolicy: autotuned operating point ==")
    index = build_doc_graph(index, degree=8, batch=256)   # refine tier
    held_out, rest = queries[:64], queries[64:]
    _, eids = exact_search(docs, held_out, 10)
    # small coupled grid: block budget down vs refine rounds up
    grid = [SearchParams(k=10, cut=10, block_budget=b, policy="budget",
                         graph_degree=d, refine_rounds=r)
            for b in (4, 8, 16) for d, r in ((0, 0), (8, 1))]
    index = tune_and_attach(index, held_out, np.asarray(eids),
                            targets=[0.9], grid=grid)
    pol = index.tuned[0]
    print(f"   tuned@{pol.target}: block_budget={pol.block_budget} "
          f"refine_rounds={pol.refine_rounds} "
          f"(measured recall={pol.measured_recall:.3f}, "
          f"{pol.measured_cost:.0f} docs/query)")
    params = SearchParams.from_tuned(index, target=0.9)
    server = SeismicServer(index, params, max_batch=128)  # validates
    result = server.search(rest)
    _, exact_ids = exact_search(docs, rest, 10)
    rec = np.mean([recall_at_k(result.ids[q], np.asarray(exact_ids[q]))
                   for q in range(rest.n)])
    print(f"   served {rest.n} fresh queries at recall@10={rec:.3f}, "
          f"mean docs evaluated={result.docs_evaluated.mean():.0f}")


def quality_demo(docs, queries, index):
    """The quality plane: serve a tuned operating point with a shadow
    auditor sampling live traffic, print the live-recall / loss-funnel
    report, and poke the /quality.json + /healthz endpoints."""
    import json
    import urllib.request

    from repro.obs import (Observability, ShadowAuditor, sample_stats,
                           start_exporter)
    from repro.obs.report import funnel_table

    print("== Quality plane: shadow-oracle recall auditing ==")
    held_out = queries[:64]
    _, eids = exact_search(docs, held_out, 10)
    grid = [SearchParams(k=10, cut=10, block_budget=b, policy="budget")
            for b in (4, 8, 16)]
    index = tune_and_attach(index, held_out, np.asarray(eids),
                            targets=[0.9], grid=grid)
    params = SearchParams.from_tuned(index, target=0.9)
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    obs = Observability.create(stage_sample_every=0)
    # target auto-resolves from the TunedPolicy matching `params`;
    # the reference enables the query-drift gauges
    obs.auditor = ShadowAuditor(
        index, params, obs.registry, audit_sample_every=4,
        queue_bound=256,
        reference=sample_stats(np.asarray(held_out.coords),
                               np.asarray(held_out.vals), index.dim))
    server = AsyncSeismicServer(
        index, params, max_batch=32, query_nnz=queries.nnz_max,
        deadline_s=0.005, cache_size=0, obs=obs)
    with server, obs.auditor:
        futs = [server.submit(coords[i % queries.n],
                              vals[i % queries.n]) for i in range(256)]
        for f in futs:
            f.wait()
        obs.auditor.drain()          # let the worker catch up
        with start_exporter(obs.registry, obs.tracer,
                            quality=obs.auditor.snapshot) as exp:
            with urllib.request.urlopen(exp.url + "/healthz") as r:
                health = json.load(r)
            with urllib.request.urlopen(exp.url + "/quality.json") as r:
                snap = json.load(r)
    print(f"   GET /healthz -> {health}")
    print(f"   GET /quality.json (every 4th of {snap['served']} "
          f"served requests audited):")
    for line in funnel_table(snap).splitlines():
        print("     " + line)


def decode_demo():
    print("== LMDecoder: KV-cache batched generation ==")
    bundle = get_bundle("gemma3-27b")          # reduced: dual-cache path
    cfg = bundle.reduced
    params = bundle.init(jax.random.PRNGKey(0), cfg, {})
    dec = LMDecoder(params, cfg, batch=4, max_seq=64)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 8))
    t0 = time.time()
    out = dec.generate(prompts.astype(np.int32), n_steps=24, greedy=True)
    print(f"   generated {out.shape} tokens in {time.time()-t0:.1f}s")
    print("   sample:", out[0].tolist())


if __name__ == "__main__":
    docs, queries, index = build_demo_index()
    retrieval_demo(docs, queries, index)
    async_demo(queries, index)
    replica_demo(docs, queries, index)
    observability_demo(queries, index)
    tuned_demo(docs, queries, index)
    quality_demo(docs, queries, index)
    decode_demo()
