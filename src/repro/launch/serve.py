"""Production serving launcher: builds a (doc-sharded) Seismic index
over a synthetic collection and serves batched queries; reports
throughput, recall, and docs-evaluated telemetry.

  PYTHONPATH=src python -m repro.launch.serve --n-docs 8192 --queries 256
  PYTHONPATH=src python -m repro.launch.serve --doc-shards 4

``--doc-shards`` shards the corpus over the visible devices (one shard
per device when there are enough), one data-parallel row per group of
``doc_shards`` devices.
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=2048)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--cut", type=int, default=10)
    ap.add_argument("--doc-shards", type=int, default=1)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import SeismicConfig, SearchParams, build_index
    from repro.core.baselines import exact_search
    from repro.core.oracle import recall_at_k
    from repro.data import SyntheticSparseConfig, make_collection
    from repro.serve.engine import SeismicServer
    from repro.sparse.ops import PaddedSparse

    cfg = SyntheticSparseConfig(dim=args.dim, n_docs=args.n_docs,
                                n_queries=args.queries, doc_nnz=96,
                                query_nnz=32)
    docs_np, queries_np, _ = make_collection(cfg)
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), docs_np.dim)
    queries = PaddedSparse(jnp.asarray(queries_np.coords),
                           jnp.asarray(queries_np.vals), queries_np.dim)
    icfg = SeismicConfig(lam=192, beta=12, alpha=0.4, block_cap=32,
                         summary_nnz=48)
    p = SearchParams(k=args.k, cut=args.cut, block_budget=args.budget,
                     policy="adaptive")

    if args.doc_shards > 1:
        from repro.core.distributed import (build_sharded_index,
                                            make_distributed_search,
                                            place_on_mesh)
        n_dev = len(jax.devices())
        mesh = jax.make_mesh((n_dev // args.doc_shards, args.doc_shards),
                             ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        stacked = place_on_mesh(
            build_sharded_index(docs_np, icfg, args.doc_shards), mesh)
        search = make_distributed_search(mesh, p)
        with jax.set_mesh(mesh):
            t0 = time.time()
            s, ids = jax.jit(search)(stacked, queries.coords, queries.vals)
            jax.block_until_ready(s)
            dt = time.time() - t0
        ids = np.asarray(ids)
    else:
        index = build_index(docs, icfg, list_chunk=32)
        server = SeismicServer(index, p, max_batch=min(args.queries, 256))
        t0 = time.time()
        result = server.search(queries)
        dt = time.time() - t0
        ids = result.ids
        print(f"docs evaluated (mean): {result.docs_evaluated.mean():.0f}")

    _, exact_ids = exact_search(docs, queries, args.k)
    rec = np.mean([recall_at_k(ids[q], np.asarray(exact_ids[q]))
                   for q in range(args.queries)])
    print(f"{args.queries} queries in {dt*1000:.0f} ms "
          f"({dt/args.queries*1e6:.0f} us/query, includes first-batch "
          f"compile)  recall@{args.k}={rec:.3f}")


if __name__ == "__main__":
    main()
