#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for several cells that
share one corpus and index, one build per seed, in one process.

    python bench/readings.py --workloads a,b --seeds 1,2,3 --seconds 5 [--control]

For each seed it builds the index once, runs each cell's server through
a short window of the cell's own mix at the cell's own sizes, frees the
index, runs the reference and prints each cell's checks as one JSON
line. With ``--control`` the index is built with the program's own
lower-precision forward plane (``fwd_quant``: u8 values with per-document
scale, one step below the configured bf16), which has to come out not
correct; the reference stays as configured. The limits of the checks
are set from these readings (PERF.md); the benchmark's runs never call
this script.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from lib import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cells = [spec.workload(bench, n) for n in args.workloads.split(",")]
    cfgs = [copy.deepcopy(spec.load_config(bench, c["config"]))
            for c in cells]
    mixes = [spec.load_traffic(c["traffic"]) for c in cells]
    for cfg in cfgs:
        cfg["index"]["fwd_quant"] = args.control
        if (cfg["corpus"], cfg["index"]) != (cfgs[0]["corpus"],
                                             cfgs[0]["index"]):
            raise SystemExit("readings: the cells do not share one index")
    if len({m["pool"] for m in mixes}) != 1:
        raise SystemExit("readings: the cells do not share one query pool")
    devs = run.require_devices(1)
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        prep = run.prepare(cfgs[0], mixes[0], seed, devs)
        windows = []
        for cell, cfg, mix in zip(cells, cfgs, mixes):
            server = run.start_server(prep, cfg, mix)
            windows.append(run.measure(server, prep, cfg, mix, seed,
                                       args.seconds, False, devs))
            del server
        prep.pop("index")
        gc.collect()
        # the reference as configured (bf16 plane), control or not
        ref_cfg = spec.load_config(bench, cells[0]["config"])
        ref = run.reference_of(prep, ref_cfg, devs)
        for cell, cfg, w in zip(cells, cfgs, windows):
            checks = run.judge(w, prep, ref, cfg)
            print(json.dumps({
                "workload": cell["name"], "seed": seed,
                "control": args.control,
                "correct": all(c.ok for c in checks),
                **{c.name: c.value for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
