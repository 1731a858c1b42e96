"""Find the benchmark's parts by name.

``BENCHMARK.json`` at the root of the checkout names the cells; each
cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); each per-layer metric
has a reader of its own (``bench/metrics/<metric>.py``, a function
``read(run) -> float | None``). Adding a cell, a configuration, a mix
or a metric adds files and entries; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or malformed."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def check_names(spec: dict) -> list[str]:
    """Every name and unit of ``spec`` against the allowed characters;
    returns the offending entries (empty when all are sound)."""
    bad = []
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w["config"] for w in spec["workloads"]]
    names += [w["traffic"] for w in spec["workloads"]]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    metrics = spec["end_to_end"] + spec["per_layer"]
    names += [m["name"] for m in metrics]
    bad += [n for n in names if not NAME_RE.match(n)]
    bad += [m["unit"] for m in metrics if not UNIT_RE.match(m["unit"])]
    bad += [m["better"] for m in metrics
            if m["better"] not in ("lower", "higher")]
    for kind in ("configs", "workloads"):
        seen = [e["name"] for e in spec[kind]]
        bad += sorted({n for n in seen if seen.count(n) > 1})
    seen = [m["name"] for m in metrics]
    bad += sorted({n for n in seen if seen.count(n) > 1})
    return bad


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def load_config(spec: dict, name: str, root: str = ROOT) -> dict:
    """The configuration as it is run (``file`` of its entry)."""
    return _read_json(os.path.join(root, config_entry(spec, name)["file"]))


def shards(cfg: dict) -> int:
    """Shards the configuration splits its corpus into (``"layout":
    {"shards": N}``); 0 where it has no ``layout`` and one chip holds
    the whole corpus."""
    layout = cfg.get("layout")
    if layout is None:
        return 0
    n = layout.get("shards") if isinstance(layout, dict) else None
    if type(n) is not int or n < 1:
        raise SpecError(f"config {cfg.get('name')!r}: layout.shards must "
                        f"be a whole number of at least 1, got {n!r}")
    return n


def check_layout(cell: dict, cfg: dict, mix: dict) -> None:
    """A sharded configuration builds one shard on each of the cell's
    chips and serves them through ``submit``; refuse a cell that breaks
    either rule."""
    n = shards(cfg)
    if n and n != cell["chips"]:
        raise SpecError(
            f"cell {cell['name']!r}: layout.shards {n} of config "
            f"{cell['config']!r} must equal the cell's chips "
            f"({cell['chips']}): one shard is built on each chip")
    if n and mix["api"] != "submit":
        raise SpecError(
            f"cell {cell['name']!r}: a sharded corpus is served only "
            f"through submit (the replica server's shard mode); traffic "
            f"{cell['traffic']!r} uses {mix['api']!r}")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    listing it under ``workloads``, or every one that lists none."""
    return [m for m in spec[kind]
            if cell in m.get("workloads", [cell])]


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader for metric {name!r} "
                        f"(bench/metrics/{name}.py)")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
