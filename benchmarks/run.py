"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,table1,...]
                                            [--smoke] [--artifacts DIR]

``--artifacts DIR`` persists one ``BENCH_<module>.json`` per module —
the machine-readable benchmark trail (name, git revision, runtime
config, every row, and the verdict of any ``gate_*`` derived value) —
which CI uploads as a build artifact so a regression can be traced to
the exact run that introduced it. ``--smoke`` is forwarded to modules
whose ``run()`` accepts it (the CI-sized path).
"""
from __future__ import annotations

import argparse
import inspect
import json
import platform
import subprocess
import sys
import time
from pathlib import Path

MODULES = ["fig1_concentration", "table1_tradeoff", "table2_space_build",
           "fig5_blocking", "fig6_summaries", "pipeline_throughput",
           "serving_load", "graph_refine", "autotune",
           "kernel_microbench", "obs_overhead", "mutation"]


def parse_row(line: str) -> dict:
    """One ``name,us_per_call,k=v;k=v`` row -> plain dict."""
    name, us, derived = line.split(",", 2)
    d = {}
    for kv in derived.split(";"):
        if "=" in kv:
            k, v = kv.split("=", 1)
            d[k] = v
    return {"name": name, "us_per_call": float(us), "derived": d}


def gate_verdicts(rows: list[dict]) -> dict:
    """Every ``gate_*`` derived value across the module's rows.
    Stringly ``True``/``False`` (the row format) -> real booleans."""
    out = {}
    for r in rows:
        for k, v in r["derived"].items():
            if k.startswith("gate_"):
                out[f"{r['name']}.{k}"] = v == "True"
    return out


def git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, check=True, timeout=10).stdout.strip()
    except Exception:   # noqa: BLE001 — artifacts must not need git
        return "unknown"


def write_artifact(art_dir: Path, mod_name: str, rows: list[dict],
                   *, smoke: bool, elapsed_s: float,
                   error: str | None = None) -> None:
    import jax
    gates = gate_verdicts(rows)
    art = {
        "name": mod_name,
        "git_rev": git_rev(),
        "unix_time": time.time(),
        "config": {
            "smoke": smoke,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "jax": jax.__version__,
            "jax_backend": jax.default_backend(),
        },
        "elapsed_s": elapsed_s,
        "rows": rows,
        "gates": gates,
        "verdict": ("error" if error is not None
                    else "fail" if gates and not all(gates.values())
                    else "pass"),
        "error": error,
    }
    art_dir.mkdir(parents=True, exist_ok=True)
    path = art_dir / f"BENCH_{mod_name}.json"
    path.write_text(json.dumps(art, indent=1) + "\n")
    print(f"# artifact {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated prefixes (fig1,table1,...)")
    ap.add_argument("--smoke", action="store_true",
                    help="forward smoke=True to modules that take it")
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="persist BENCH_<module>.json artifacts here")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = args.only.split(",") if args.only else None
    art_dir = Path(args.artifacts) if args.artifacts else None

    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        t0 = time.time()
        rows: list[dict] = []
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kwargs = {}
            sig = inspect.signature(mod.run).parameters
            if args.smoke and "smoke" in sig:
                kwargs["smoke"] = True
            if art_dir is not None and "artifacts_dir" in sig:
                kwargs["artifacts_dir"] = art_dir  # side artifacts
                art_dir.mkdir(parents=True, exist_ok=True)
            for line in mod.run(**kwargs):
                print(line)
                rows.append(parse_row(line))
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
            if art_dir is not None:
                write_artifact(art_dir, mod_name, rows, smoke=args.smoke,
                               elapsed_s=time.time() - t0)
            if not all(gate_verdicts(rows).values()):
                failures += 1
                print(f"# {mod_name} GATE FAILED", file=sys.stderr)
        except Exception as e:  # keep the harness going
            failures += 1
            print(f"# {mod_name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            if art_dir is not None:
                write_artifact(art_dir, mod_name, rows, smoke=args.smoke,
                               elapsed_s=time.time() - t0,
                               error=f"{type(e).__name__}: {e}")
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
