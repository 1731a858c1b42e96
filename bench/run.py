#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). A run:

1. refuses to run without a TPU, or with fewer chips than the cell asks;
2. set-up, timed as ``setup_s``: draws the corpus and the query pool
   from ``--seed``, builds the index on the chip over the lists the
   pool probes (with ``"layout": {"shards": N}`` in the configuration,
   one shard of the corpus on each of the cell's N chips), starts the
   server and compiles every launch shape the mix uses;
3. drives the server with the mix for ``--seconds`` (with ``--trace 1``
   under the profiler);
4. reads the peak device memory (the fullest chip's), frees the server
   and the index, and runs the plain reference over the same corpus and
   queries (on a sharded corpus, one range of documents on each chip);
5. compares every answer of the window with the reference
   (``lib/check.py``) and prints the checks on standard error, and the
   result as one JSON line, last on standard output.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics; each metric is computed by its own
reader, ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import check, gen, reference, spec, traffic, workbytes, xplane  # noqa: E402
from lib.peaks import peaks  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LATE_S = 60.0          # how long an answer may come after the close


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_devices(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, found "
                         f"{len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Backend compilations while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1


# ----------------------------------------------------------------- set-up

def make_data(cfg: dict, mix: dict, seed: int):
    c = cfg["corpus"]
    g = gen.SyntheticSparseConfig(
        dim=c["dim"], n_docs=c["n_docs"], n_queries=mix["pool"],
        doc_nnz=c["doc_nnz"], query_nnz=c["query_nnz"],
        n_topics=c["n_topics"], topic_coords=c["topic_coords"],
        zipf_a=c["zipf_a"], value_sigma=c["value_sigma"],
        doc_topic_mix=c["doc_topic_mix"], seed=seed)
    return gen.make_collection(g)


def probed(q_coords: np.ndarray, q_vals: np.ndarray, cut: int) -> np.ndarray:
    """[Q, cut] coordinates of each query's ``cut`` largest values
    (ties to the lower slot, padding to coordinate 0)."""
    order = np.argsort(-q_vals, axis=1, kind="stable")[:, :cut]
    c = np.take_along_axis(q_coords, order, axis=1)
    return np.where(np.take_along_axis(q_vals, order, axis=1) > 0, c, 0)


def build(cfg: dict, doc_coords, doc_vals, lists, dev):
    import jax
    from repro.core import SeismicConfig, build_index
    from repro.sparse.ops import PaddedSparse
    docs = jax.device_put(PaddedSparse(doc_coords, doc_vals,
                                       cfg["corpus"]["dim"]), dev)
    index = build_index(docs, SeismicConfig(**cfg["index"]),
                        list_chunk=cfg["build"]["list_chunk"], lists=lists)
    return jax.block_until_ready(index)


def build_sharded(cfg: dict, doc_coords, doc_vals, lists, devs):
    """The program's sharded build from the host arrays: shard s of
    ``spec.shards(cfg)`` built on its own device of ``devs``, so that
    no chip holds the whole corpus."""
    import jax
    from repro.core import SeismicConfig
    from repro.core.distributed import build_sharded_index
    from repro.sparse.ops import PaddedSparse
    index = build_sharded_index(
        PaddedSparse(doc_coords, doc_vals, cfg["corpus"]["dim"]),
        SeismicConfig(**cfg["index"]), spec.shards(cfg),
        list_chunk=cfg["build"]["list_chunk"], devices=devs, lists=lists)
    return jax.block_until_ready(index)


def make_server(index, cfg: dict, mix: dict):
    from repro.retrieval import SearchParams
    params = SearchParams(**cfg["search"])
    if mix["api"] == "search":
        from repro.serve import SeismicServer
        return SeismicServer(index, params, max_batch=cfg["serve"]["max_batch"])
    srv = mix["server"]
    widths = srv.get("launch_widths")
    kw = dict(max_batch=cfg["serve"]["max_batch"],
              query_nnz=cfg["serve"]["query_nnz"],
              launch_widths=None if widths is None else tuple(widths),
              deadline_s=srv["deadline_ms"] * 1e-3,
              queue_bound=srv["queue_bound"], cache_size=srv["cache_size"],
              coalesce=srv["coalesce"])
    if spec.shards(cfg):
        from repro.serve import ReplicaSeismicServer
        return ReplicaSeismicServer(index, params, mode="shard",
                                    n_docs=cfg["corpus"]["n_docs"], **kw)
    from repro.serve import AsyncSeismicServer
    return AsyncSeismicServer(index, params, **kw)


# ----------------------------------------------------------------- window

def window_open(server, mix, seed, seconds, q_coords, q_vals, k, annotate):
    """Open loop through ``server.submit``. Returns the per-request
    arrays of the window."""
    due, qidx = traffic.open_loop_schedule(seed, mix, seconds,
                                           q_coords.shape[0])
    start, sent, futs = traffic.run_open_loop(
        server.submit, q_coords, q_vals, due, qidx, annotate=annotate)
    n = due.size
    ids = np.full((n, k), -1, np.int64)
    scores = np.full((n, k), -np.inf, np.float32)
    ev = np.zeros(n, np.int64)
    done_t = np.full(n, np.nan)
    close = start + seconds
    for i, f in enumerate(futs):
        f.wait(max(0.0, close + LATE_S - time.monotonic()))
        if f.status != "done":
            continue
        r = f.result()
        ids[i], scores[i], ev[i] = r.ids, r.scores, r.docs_evaluated
        # the server's submit stamp + its submit->fulfil time; ``sent``
        # is read just before submit, so this is the earliest the
        # answer can have been ready
        done_t[i] = sent[i] + r.latency_s
    answered = ~np.isnan(done_t)
    late = sent - (start + due)
    return dict(ids=ids, scores=scores, ev=ev, qidx=qidx, answered=answered,
                latency_ms=(done_t - start - due)[answered] * 1e3,
                window_s=float(np.nanmax(done_t, initial=close) - start),
                sender_late_ms=(float(np.median(late) * 1e3),
                                float(late.max() * 1e3)) if n else (0.0, 0.0))


def window_closed(server, seconds, q_coords, q_vals, dim, annotate):
    """Closed loop: the whole pool in every ``server.search`` call."""
    from repro.sparse.ops import PaddedSparse
    pool = PaddedSparse(q_coords, q_vals, dim)
    window_s, outs = traffic.run_closed_loop(lambda: server.search(pool),
                                             seconds, annotate=annotate)
    n_calls = len(outs)
    qn = q_coords.shape[0]
    return dict(ids=np.concatenate([o.ids for o in outs]).astype(np.int64),
                scores=np.concatenate([o.scores for o in outs]),
                ev=np.concatenate([o.docs_evaluated for o in outs]),
                qidx=np.tile(np.arange(qn), n_calls),
                answered=np.ones(n_calls * qn, bool), latency_ms=None,
                window_s=window_s, sender_late_ms=None)


def trace_summary(log_dir: str):
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    return xplane.reduce(xplane.load(paths[0]))


# ----------------------------------------------------------------- phases

def prepare(cfg: dict, mix: dict, seed: int, devs) -> dict:
    """Data from ``seed`` and the index built over the lists the query
    pool probes, on ``devs[0]`` or, for a sharded configuration, one
    shard on each of ``devs``; with the per-query live-block counts and
    row sizes the necessary-bytes metric needs."""
    t0 = time.perf_counter()
    doc_coords, doc_vals, q_coords, q_vals = make_data(cfg, mix, seed)
    t_data = time.perf_counter() - t0
    probed_q = probed(q_coords, q_vals, cfg["search"]["cut"])
    lists = np.unique(probed_q)
    sharded = spec.shards(cfg) > 0
    if sharded:
        index = build_sharded(cfg, doc_coords, doc_vals, lists, devs)
    else:
        index = build(cfg, doc_coords, doc_vals, lists, devs[0])
    live_per_list = np.asarray((index.block_len > 0).sum(axis=-1))
    if sharded:
        # [shards, lists]: a query routes over every shard's blocks
        live_per_list = live_per_list.sum(axis=0)
    log(f"bench: data {t_data:.3f} s, build "
        f"{time.perf_counter() - t0 - t_data:.3f} s over {lists.size} lists")
    return dict(doc_coords=doc_coords, doc_vals=doc_vals, q_coords=q_coords,
                q_vals=q_vals, index=index,
                live_q=workbytes.live_blocks_probed(live_per_list, probed_q),
                row_b=(workbytes.summary_row_bytes(index),
                       workbytes.forward_row_bytes(index)))


def start_server(prep: dict, cfg: dict, mix: dict):
    """The cell's server, with every launch shape of the mix compiled."""
    server = make_server(prep["index"], cfg, mix)
    if mix["api"] == "search":
        from repro.sparse.ops import PaddedSparse
        top = cfg["serve"]["max_batch"]    # one chunk compiles the program
        server.search(PaddedSparse(prep["q_coords"][:top],
                                   prep["q_vals"][:top], cfg["corpus"]["dim"]))
    else:
        server.start()
    return server


def measure(server, prep: dict, cfg: dict, mix: dict, seed: int,
            seconds: float, trace: bool, devs) -> dict:
    """The measured window, then the peak device memory; stops the
    server. With ``trace`` the window runs under the profiler and its
    trace is reduced (``summary``)."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    compiles = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles.active = True
    try:
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with annotate("bench.window"):
            if mix["api"] == "search":
                w = window_closed(server, seconds, prep["q_coords"],
                                  prep["q_vals"], cfg["corpus"]["dim"],
                                  annotate)
            else:
                w = window_open(server, mix, seed, seconds, prep["q_coords"],
                                prep["q_vals"], cfg["search"]["k"], annotate)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
        compiles.active = False
    w["compiles"] = compiles.count
    w["memory_peak"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    w["telemetry"] = None
    if mix["api"] == "submit":
        w["telemetry"] = server.telemetry_export()
        server.stop()
    w["summary"] = None
    if trace_dir:
        try:
            w["summary"] = trace_summary(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return w


def reference_of(prep: dict, cfg: dict, devs) -> dict:
    """The plain reference over the pool, on ``devs[0]`` or, for a
    sharded configuration, one range of the corpus on each of ``devs``;
    run once the index is freed."""
    stored_vals = reference.stored(prep["doc_vals"],
                                   cfg["index"]["fwd_dtype"])
    q_dense = reference.dense_queries(prep["q_coords"], prep["q_vals"],
                                      cfg["corpus"]["dim"])
    if spec.shards(cfg):
        exact_scores, exact_ids = reference.exact_topk_ranged(
            prep["doc_coords"], stored_vals, q_dense, cfg["search"]["k"],
            devices=devs)
    else:
        exact_scores, exact_ids = reference.exact_topk(
            prep["doc_coords"], stored_vals, q_dense, cfg["search"]["k"],
            device=devs[0])
    return dict(stored_vals=stored_vals, q_dense=q_dense,
                exact_scores=exact_scores, exact_ids=exact_ids)


def judge(w: dict, prep: dict, ref: dict, cfg: dict) -> list:
    return check.compare(
        ids=w["ids"], scores=w["scores"], qidx=w["qidx"],
        answered=w["answered"], exact_ids=ref["exact_ids"],
        exact_scores=ref["exact_scores"], q_dense=ref["q_dense"],
        doc_coords=prep["doc_coords"], doc_vals=ref["stored_vals"],
        recall_target=cfg["checks"]["recall_at_10"],
        score_gap_limit=cfg["checks"]["score_gap"])


# ------------------------------------------------------------------- main

def run_cell(cell: dict, cfg: dict, mix: dict, metric_defs: list,
             seed: int, seconds: float, trace: bool, devs,
             chip: dict) -> tuple[dict, list]:
    """One run of ``cell`` on ``devs``; returns (result, checks)."""
    import jax
    spec.check_layout(cell, cfg, mix)
    dev = devs[0]
    readers = {m["name"]: spec.metric_reader(m["name"]) for m in metric_defs}
    t_setup = time.perf_counter()
    prep = prepare(cfg, mix, seed, devs)
    server = start_server(prep, cfg, mix)
    setup_s = time.perf_counter() - t_setup
    log(f"bench: setup {setup_s:.3f} s")
    w = measure(server, prep, cfg, mix, seed, seconds, trace, devs)
    del server
    prep.pop("index")
    gc.collect()
    t_ref = time.perf_counter()
    checks = judge(w, prep, reference_of(prep, cfg, devs), cfg)
    ref_s = time.perf_counter() - t_ref

    done = w["answered"]
    need = workbytes.necessary_bytes(prep["live_q"][w["qidx"][done]],
                                     w["ev"][done], *prep["row_b"])
    run = dict(setup_s=setup_s, window_s=w["window_s"],
               n_completed=int(done.sum()), latency_ms=w["latency_ms"],
               recall=next(c.value for c in checks
                           if c.name == "recall_at_10"),
               telemetry=w["telemetry"], trace=w["summary"],
               necessary_bytes=float(need.sum()), peaks=chip)
    metrics = {}
    for m in metric_defs:
        v = readers[m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    log(f"bench: window {w['window_s']:.3f} s, {run['n_completed']} of "
        f"{done.size} answered, compilations in window {w['compiles']}, "
        f"reference {ref_s:.3f} s, peak memory {w['memory_peak']} bytes")
    if w["sender_late_ms"] is not None:
        log(f"bench: sender late by median {w['sender_late_ms'][0]:.4f} "
            f"ms, max {w['sender_late_ms'][1]:.4f} ms")
    if w["telemetry"] is not None:
        tel = w["telemetry"]
        widths = {k: v for k, v in tel["counters"].items()
                  if k.startswith("launch_width_")}
        log(f"bench: queue depth max {tel['queue']['depth_max']}, "
            f"launches {widths}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": w["memory_peak"]}
    out = {"correct": all(c.ok for c in checks),
           "attempted": int(done.size), "failed": int((~done).sum()),
           "metrics": metrics, "device": device}
    summary = w["summary"]
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                              "rule": c.rule} for c in checks}
    return out, checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    metric_defs = spec.cell_metrics(
        bench, cell["name"], "per_layer" if args.trace else "end_to_end")
    devs = require_devices(cell["chips"])
    import repro.serve  # noqa: F401  (the system under test, found early)
    chip = peaks(devs[0].device_kind)
    cache_dir = enable_compile_cache()
    log(f"bench: {cell['name']} seed {args.seed} on "
        f"{devs[0].device_kind} x{len(devs)}; compile cache {cache_dir}")
    out, checks = run_cell(cell, cfg, mix, metric_defs, args.seed,
                           args.seconds, bool(args.trace), devs, chip)
    for c in checks:
        log(c.line())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
