#!/usr/bin/env python3
"""Find a cell's operating points once, on the chip: the knee of the
online server and the block budget that meets a recall target.

    python bench/calibrate.py --config <config> --seeds 0,1 \
        [--budgets 64,96,128,...] [--rates 20,24,...] [--sweep-seconds 5] \
        [--window-lengths 20,40 --window-repeats 6]

For each seed: draws the corpus and a pool of 1,024 queries as a run of
the benchmark does, builds the index over the lists the pool probes,
and computes the exact reference up front. Then

* budget search: for each block budget, the bulk server's recall@10 on
  the pool and its queries per second over a few seconds (``max_batch``
  halved where the config's does not fit, as ``rehearse.py`` shows);
* knee sweep (first seed only, at the config's own budget): the online
  server under the ``online`` mix's open loop at each rate, with the
  completed rate, p50/p95 from the due time, and whether the rate is
  sustained (``sustained``: the completed rate against the offered, the
  last second's p95 against the first's);
* window lengths (first seed only): the online mix at its own rate,
  several windows of each length, for the spread a run's length buys.

Prints one JSON line per reading. This is a tool for defining cells;
the benchmark's runs never call it.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from lib import check, reference, spec, traffic  # noqa: E402


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def budget_search(index, cfg, q_coords, q_vals, exact_ids, budgets, seed,
                  seconds):
    from repro.sparse.ops import PaddedSparse
    dim = cfg["corpus"]["dim"]
    pool = PaddedSparse(q_coords, q_vals, dim)
    for b in budgets:
        c = copy.deepcopy(cfg)
        c["search"]["block_budget"] = b
        if b > 256:                    # rehearse.py: 256 wide does not fit
            c["serve"]["max_batch"] = 128
        server = run.make_server(index, c, {"api": "search", "server": {}})
        t = time.perf_counter()
        res = server.search(pool)
        compile_s = time.perf_counter() - t
        window_s, outs = traffic.run_closed_loop(lambda: server.search(pool),
                                                 seconds)
        rec = check.recall(res.ids.astype(np.int64),
                           np.arange(q_coords.shape[0]), exact_ids)
        emit(kind="budget", seed=seed, block_budget=b,
             max_batch=c["serve"]["max_batch"], recall_at_10=float(rec.mean()),
             docs_evaluated_mean=float(res.docs_evaluated.mean()),
             qps=len(outs) * q_coords.shape[0] / window_s,
             first_call_s=compile_s)


def sustained(attempted: int, seconds: float, completed_qps: float,
              p95_first_s_ms: float, p95_last_s_ms: float) -> bool:
    """Whether a rate is sustained: at least 95% of the offered rate
    completes, and the p95 of requests due in the last second is at most
    1.5 times that of the first (a backlog that grows through the window
    fails the second test; one that is already deep in the first second
    fails the first)."""
    return (completed_qps >= 0.95 * attempted / seconds
            and p95_last_s_ms <= 1.5 * p95_first_s_ms)


def open_window(index, cfg, mix, q_coords, q_vals, exact_ids, sched_seed,
                seconds) -> dict:
    """One open-loop window of ``mix`` on a fresh online server; its
    schedule is drawn from ``sched_seed``."""
    server = run.make_server(index, cfg, mix)
    server.start()
    try:
        w = run.window_open(server, mix, sched_seed, seconds, q_coords,
                            q_vals, cfg["search"]["k"], None)
    finally:
        server.stop()
    lat = w["latency_ms"]
    due, _ = traffic.open_loop_schedule(sched_seed, mix, seconds,
                                        q_coords.shape[0])
    attempted = int(due.size)
    due = due[w["answered"]]
    rec = check.recall(w["ids"][w["answered"]], w["qidx"][w["answered"]],
                       exact_ids)
    return dict(answered=int(w["answered"].sum()), attempted=attempted,
                completed_qps=int(w["answered"].sum()) / w["window_s"],
                p50_ms=float(np.percentile(lat, 50)),
                p95_ms=float(np.percentile(lat, 95)),
                p95_first_s_ms=float(np.percentile(lat[due < 1.0], 95)),
                p95_last_s_ms=float(np.percentile(lat[due >= seconds - 1.0],
                                                  95)),
                sender_late_ms=w["sender_late_ms"],
                recall_at_10=float(rec.mean()))


def knee_sweep(index, cfg, mix, q_coords, q_vals, exact_ids, rates, seed,
               seconds):
    for rate in rates:
        r = open_window(index, cfg, dict(mix, rate_qps=rate), q_coords,
                        q_vals, exact_ids, seed, seconds)
        emit(kind="knee", seed=seed, rate_qps=rate, **r,
             sustained=sustained(r["attempted"], seconds,
                                 r["completed_qps"], r["p95_first_s_ms"],
                                 r["p95_last_s_ms"]))


def window_repeats(index, cfg, mix, q_coords, q_vals, exact_ids, lengths,
                   repeats, seed):
    """The online mix at its own rate, ``repeats`` windows of each
    length on one index, schedule seeds ``seed * 1000 + i``: how the
    spread of p50/p95 falls with the window's length."""
    for seconds in lengths:
        for i in range(repeats):
            r = open_window(index, cfg, mix, q_coords, q_vals, exact_ids,
                            seed * 1000 + i, seconds)
            emit(kind="window", seed=seed, schedule_seed=seed * 1000 + i,
                 seconds=seconds, rate_qps=mix["rate_qps"], **r)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--budgets", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--budget-seconds", type=float, default=3.0)
    ap.add_argument("--sweep-seconds", type=float, default=5.0)
    ap.add_argument("--window-lengths", default="")
    ap.add_argument("--window-repeats", type=int, default=6)
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, args.config)
    mix = spec.load_traffic("online")
    devs = run.require_devices(1)
    run.enable_compile_cache()
    dev = devs[0]
    dim = cfg["corpus"]["dim"]
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        dc, dv, qc, qv = run.make_data(cfg, mix, seed)
        pq = run.probed(qc, qv, cfg["search"]["cut"])
        index = run.build(cfg, dc, dv, np.unique(pq), dev)
        stored = reference.stored(dv, cfg["index"]["fwd_dtype"])
        t_ref = time.perf_counter()
        _, exact_ids = reference.exact_topk(
            dc, stored, reference.dense_queries(qc, qv, dim),
            cfg["search"]["k"], device=dev)
        emit(kind="setup", seed=seed, seconds=time.perf_counter() - t,
             reference_s=time.perf_counter() - t_ref,
             lists=int(np.unique(pq).size))
        if args.budgets:
            budget_search(index, cfg, qc, qv, exact_ids,
                          [int(b) for b in args.budgets.split(",")], seed,
                          args.budget_seconds)
        if args.rates and n == 0:
            knee_sweep(index, cfg, mix, qc, qv, exact_ids,
                       [float(r) for r in args.rates.split(",")], seed,
                       args.sweep_seconds)
        if args.window_lengths and n == 0:
            window_repeats(index, cfg, mix, qc, qv, exact_ids,
                           [float(x) for x in args.window_lengths.split(",")],
                           args.window_repeats, seed)
        del index
        gc.collect()             # servers hold the index in cycles
    emit(kind="device", peak_bytes=(dev.memory_stats() or {}).get(
        "peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
