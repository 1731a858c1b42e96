#!/usr/bin/env python3
"""Bring-up smoke test: serve a Seismic index at MS MARCO widths on TPU.

    python chip_smoke.py [--seed 0]             # one chip
    python chip_smoke.py --chips 4 [--seed 0]   # four chips, shard phase only

One chip: generates a 1,000,000-doc corpus at the widths and index
parameters of ``configs/seismic_msmarco.CONFIG`` (dim 30522, doc nnz
128, query nnz 48, lam 6000, beta 400, block_cap 64, summary_nnz 96,
bf16 forward plane), builds the index on the chip with
``core.build.build_index`` over the lists its queries probe, and serves 256 queries through
``serve.AsyncSeismicServer`` at the ``SHAPES["query_online"]`` point
(k=10, cut=10, block_budget=64, max_batch 256, the one launch width
compiled): once on the XLA path,
once on the Pallas kernel path (``use_kernel=True, fuse_level=0``).
Both are checked against the exact oracle computed on the chip
(recall@10 >= 0.90) and against each other.

Four chips: four shards of the one-chip size, each built on its own
chip, served through ``ReplicaSeismicServer(mode="shard")`` and
compared with ``make_distributed_search`` over a 1x4 mesh (identical
ids, recall@10 >= 0.90 against the full-corpus oracle).

Fails (non-zero exit, no result line) when JAX finds no TPU. The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_N_DOCS = 8_841_823
N_DOCS = 1_000_000        # per chip; the one cut (see CUT)
CUT = ("n_docs 1,000,000 of MS MARCO's 8,841,823 (11.3%): the index is "
       "~9.7 GB at these widths, whatever n_docs is, plus ~0.8 GB per 1M "
       "docs of forward plane, and the build's posting sort needs "
       "~1.5 GB per 1M docs on top; the full corpus does not fit one "
       "16 GiB chip")
N_QUERIES = 256
CUT_Q = 10                # probed coordinates per query (SHAPES point)
RECALL_BAR = 0.90
BUDGET_LADDER = (64, 96, 128, 192, 256)   # SHAPES point first
LIST_CHUNK = 2            # lists per build launch (gather-mode clustering
#                           holds a ~1.5 GB [lam * nnz, beta] f32 per list)
SCORE_RTOL = 1e-5


def log(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, found "
                         f"{len(devs)}")
    return devs[:n_chips]


def make_data(n_docs: int, seed: int):
    from repro.configs.seismic_msmarco import CONFIG
    from repro.data import SyntheticSparseConfig, make_collection
    cfg = SyntheticSparseConfig(dim=CONFIG.dim, n_docs=n_docs,
                                n_queries=N_QUERIES,
                                doc_nnz=CONFIG.doc_nnz,
                                query_nnz=CONFIG.query_nnz, seed=seed)
    t = time.perf_counter()
    docs, queries, _ = make_collection(cfg)
    log("data_seconds", time.perf_counter() - t)
    return docs, queries


def exact_topk_on(dev, docs, queries, k: int):
    """Oracle top-k on one device: (scores, ids) host arrays."""
    import jax
    from repro.core.baselines import exact_search
    s, i = exact_search(jax.device_put(docs, dev),
                        jax.device_put(queries, dev), k)
    return np.asarray(s), np.asarray(i)


def recall_at_10(ids: np.ndarray, exact_ids: np.ndarray) -> float:
    from repro.core.oracle import recall_at_k
    return float(np.mean([recall_at_k(ids[q], exact_ids[q])
                          for q in range(ids.shape[0])]))


def same_topk(ids_a, s_a, ids_b, s_b) -> bool:
    """Equal top-k up to ties: scores agree within SCORE_RTOL, and ids
    differ only where their scores tie (within SCORE_RTOL)."""
    if not np.allclose(s_a, s_b, rtol=SCORE_RTOL, atol=0.0):
        return False
    for ia, sa, ib in zip(ids_a, s_a, ids_b):
        diff = set(ia.tolist()) ^ set(ib.tolist())
        if diff:
            tied = [s for i, s in zip(ia, sa) if int(i) in diff]
            if not tied or not np.allclose(tied, sa[-1], rtol=SCORE_RTOL):
                return False
    return True


def serve(server, queries) -> tuple[np.ndarray, np.ndarray, int]:
    """Submit every query, wait for all; (ids, scores, failed count)."""
    futs = [server.submit(queries.coords[i], queries.vals[i])
            for i in range(queries.coords.shape[0])]
    ids = np.full((len(futs), server.params.k), -1, np.int32)
    scores = np.full((len(futs), server.params.k), -np.inf, np.float32)
    failed = 0
    for i, f in enumerate(futs):
        try:
            r = f.result(timeout=600)
        except (RuntimeError, TimeoutError) as e:
            failed += 1
            print(f"request {i} failed: {e}", flush=True)
            continue
        ids[i], scores[i] = r.ids, r.scores
    return ids, scores, failed


def serve_path(index, queries, exact_ids, budget: int, use_kernel: bool):
    """One AsyncSeismicServer run; returns (ids, scores, recall)."""
    from repro.configs.seismic_msmarco import CONFIG
    from repro.retrieval import SearchParams
    from repro.serve import AsyncSeismicServer
    p = SearchParams(k=10, cut=CUT_Q, block_budget=budget,
                     use_kernel=use_kernel, fuse_level=0)
    server = AsyncSeismicServer(index, p, max_batch=N_QUERIES,
                                launch_widths=(),
                                query_nnz=CONFIG.query_nnz)
    name = "kernel" if use_kernel else "xla"
    t = time.perf_counter()
    server.start()
    log(f"{name}_compile_warmup_seconds_budget{budget}",
        time.perf_counter() - t)
    try:
        ids, scores, failed = serve(server, queries)
    finally:
        server.stop()
    rec = recall_at_10(ids, exact_ids)
    log(f"{name}_served", ids.shape[0] - failed)
    log(f"{name}_failed", failed)
    log(f"{name}_recall_at_10_budget{budget}", rec)
    if failed:
        raise SystemExit(f"chip_smoke: {failed} {name} requests failed")
    return ids, scores, rec


def build_lists(docs, queries):
    """The lists the smoke builds: the ones its queries probe. A query
    reads only the lists of its top-``cut`` coordinates, so the served
    answers equal a full build's (tests/test_bring_up.py pins this)."""
    from repro.configs.seismic_msmarco import CONFIG
    from repro.retrieval.prep import probed_lists
    lists = probed_lists(queries.coords, queries.vals, CONFIG.dim, CUT_Q)
    with_postings = int((np.bincount(docs.coords[docs.vals > 0],
                                     minlength=CONFIG.dim) > 0).sum())
    log("lists_with_postings", with_postings)
    log("lists_built", f"{lists.size} (the lists the {N_QUERIES} queries "
        f"probe): building all {with_postings} lists with postings takes "
        "too large a share of one run")
    return lists, with_postings


def one_chip(dev, seed: int) -> None:
    import jax
    from repro.configs.seismic_msmarco import CONFIG
    from repro.core import build_index
    log("n_docs", N_DOCS)
    log("cut", CUT)
    docs_np, queries = make_data(N_DOCS, seed)
    cfg = CONFIG.index
    log("index_config", cfg)
    lists, with_postings = build_lists(docs_np, queries)
    docs = jax.device_put(docs_np, dev)
    t = time.perf_counter()     # compile every build program on one list
    jax.block_until_ready(build_index(docs, cfg, list_chunk=LIST_CHUNK,
                                      lists=lists[:1]))
    log("build_compile_seconds", time.perf_counter() - t)
    t = time.perf_counter()
    index = build_index(docs, cfg, list_chunk=LIST_CHUNK, lists=lists)
    jax.block_until_ready(index)
    dt = time.perf_counter() - t
    log("build_seconds", dt)
    log("build_seconds_per_list", dt / lists.size)
    log("full_build_estimate_seconds", dt / lists.size * with_postings)
    log("index_nbytes", index.nbytes())
    t = time.perf_counter()
    _, exact_ids = exact_topk_on(dev, docs, queries, 10)
    log("oracle_seconds", time.perf_counter() - t)
    del docs
    ref = None
    for budget in BUDGET_LADDER:
        ids, scores, rec = serve_path(index, queries, exact_ids, budget,
                                      use_kernel=False)
        if rec >= RECALL_BAR:
            ref = (ids, scores, budget)
            break
        log("below_bar", f"recall {rec} < {RECALL_BAR} at block_budget "
            f"{budget}; trying the next budget")
    if ref is None:
        raise SystemExit("chip_smoke: no block_budget in "
                         f"{BUDGET_LADDER} reaches recall {RECALL_BAR}")
    ids, scores, budget = ref
    log("block_budget", budget)
    kids, kscores, krec = serve_path(index, queries, exact_ids, budget,
                                     use_kernel=True)
    if krec < RECALL_BAR:
        raise SystemExit(f"chip_smoke: kernel path recall {krec} < "
                         f"{RECALL_BAR}")
    agree = same_topk(ids, scores, kids, kscores)
    log("kernel_matches_xla", agree)
    if not agree:
        raise SystemExit("chip_smoke: kernel and XLA top-10 disagree")


def four_chips(devs, seed: int) -> None:
    import jax
    from jax.sharding import Mesh
    from repro.configs.seismic_msmarco import CONFIG
    from repro.core.distributed import (build_sharded_index,
                                        make_distributed_search,
                                        place_on_mesh, shard_collection)
    from repro.retrieval import SearchParams
    from repro.serve import ReplicaSeismicServer
    from repro.sparse.ops import PaddedSparse
    n_shards = len(devs)
    n_docs = n_shards * N_DOCS
    log("n_docs", n_docs)
    log("n_shards", n_shards)
    docs_np, queries = make_data(n_docs, seed)
    p = SearchParams(k=10, cut=CUT_Q, block_budget=BUDGET_LADDER[0])
    lists, _ = build_lists(docs_np, queries)
    t = time.perf_counter()
    stacked = build_sharded_index(docs_np, CONFIG.index, n_shards,
                                  list_chunk=LIST_CHUNK, devices=devs,
                                  lists=lists)
    jax.block_until_ready(stacked)
    log("build_seconds", time.perf_counter() - t)
    log("shard_devices", [sorted(d.id for d in x.devices())
                          for x in jax.tree.leaves(stacked)[:1]])
    # full-corpus oracle: each chip scans its own shard, host merges
    sharded = shard_collection(docs_np, n_shards)
    per = sharded.coords.shape[1]
    t = time.perf_counter()
    with ThreadPoolExecutor(n_shards) as pool:    # one oracle per chip
        parts = list(pool.map(
            lambda s: exact_topk_on(devs[s], PaddedSparse(
                sharded.coords[s], sharded.vals[s], CONFIG.dim), queries, 10),
            range(n_shards)))
    all_s = np.concatenate([s for s, _ in parts], axis=1)
    all_i = np.concatenate([i + s * per for s, (_, i) in enumerate(parts)],
                           axis=1)
    all_s = np.where(all_i < n_docs, all_s, -np.inf)
    order = np.lexsort((all_i, -all_s), axis=1)[:, :10]
    exact_ids = np.take_along_axis(all_i, order, axis=1)
    log("oracle_seconds", time.perf_counter() - t)
    server = ReplicaSeismicServer(stacked, p, mode="shard", n_docs=n_docs,
                                  max_batch=N_QUERIES, launch_widths=(),
                                  query_nnz=CONFIG.query_nnz)
    t = time.perf_counter()
    server.start()
    log("replica_compile_warmup_seconds", time.perf_counter() - t)
    try:
        ids, _, failed = serve(server, queries)
    finally:
        server.stop()
    log("replica_served", ids.shape[0] - failed)
    log("replica_failed", failed)
    if failed:
        raise SystemExit(f"chip_smoke: {failed} replica requests failed")
    rec = recall_at_10(ids, exact_ids)
    log("replica_recall_at_10", rec)
    mesh = Mesh(np.array(devs).reshape(1, n_shards), ("data", "model"))
    search = jax.jit(make_distributed_search(mesh, p, n_docs=n_docs))
    t = time.perf_counter()
    with jax.set_mesh(mesh):
        _, dids = search(place_on_mesh(stacked, mesh), queries.coords,
                         queries.vals)
        dids = np.asarray(dids)
    log("distributed_seconds", time.perf_counter() - t)
    log("distributed_recall_at_10", recall_at_10(dids, exact_ids))
    same = bool(np.array_equal(ids, dids))
    log("replica_ids_equal_distributed", same)
    if not same:
        raise SystemExit("chip_smoke: shard-mode replica ids differ from "
                         "make_distributed_search")
    if rec < RECALL_BAR:
        raise SystemExit(f"chip_smoke: recall {rec} < {RECALL_BAR}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache
    log("compile_cache", enable_compile_cache())
    import jax
    log("device", f"{devs[0].device_kind} x{len(devs)} "
        f"({len(jax.devices())} visible)")
    t = time.perf_counter()
    if args.chips == 1:
        one_chip(devs[0], args.seed)
    else:
        four_chips(devs, args.seed)
    log("total_seconds", time.perf_counter() - t)
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"peak_bytes_in_use_dev{d.id}", stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
