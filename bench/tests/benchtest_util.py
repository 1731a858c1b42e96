"""Shared set-up of the benchmark's tests: the benchmark's own modules
on ``sys.path``, ``bench/run.py`` loaded as ``bench_run``, and a cell's
configuration and mix cut to a size a CPU test holds."""
import copy
import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import spec  # noqa: E402


def load_run():
    if "bench_run" not in sys.modules:
        s = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        sys.modules["bench_run"] = mod
    return sys.modules["bench_run"]


def tiny_cell(name: str):
    """(cell, config, mix) of ``name`` at CPU-test widths. The limits
    of ``checks`` stay the configuration's, except the recall target,
    which this size does not model."""
    bench = spec.load_benchmark()
    cell = spec.workload(bench, name)
    cfg = copy.deepcopy(spec.load_config(bench, cell["config"]))
    cfg["corpus"].update(dim=1024, n_docs=4096, doc_nnz=48, query_nnz=16,
                         n_topics=32, topic_coords=128)
    cfg["index"].update(lam=128, beta=8, block_cap=32, summary_nnz=32)
    cfg["search"].update(block_budget=16, cut=8)
    cfg["serve"].update(max_batch=32, query_nnz=16)
    cfg["checks"]["recall_at_10"] = 0.5
    mix = copy.deepcopy(spec.load_traffic(cell["traffic"]))
    mix["pool"] = 64
    if mix["api"] == "submit":
        mix["rate_qps"] = 400
    return cell, cfg, mix


def shard_layout(n: int):
    """A ``cfg_edit`` that splits the configuration's corpus into ``n``
    shards (``"layout": {"shards": n}``)."""
    def edit(cfg):
        cfg["layout"] = {"shards": n}
    return edit


def run_tiny(name: str, seed: int = 2 ** 33 + 5, seconds: float = 0.5,
             cfg_edit=None, chips: int | None = None):
    """One run of a cut-down cell on the CPU, past the harness's look
    for a chip, on the cell's chips (or ``chips``) of the CPU devices;
    returns {check name: Check} and the result."""
    import jax
    run = load_run()
    cell, cfg, mix = tiny_cell(name)
    if chips is not None:
        cell = dict(cell, chips=chips)
    if cfg_edit is not None:
        cfg_edit(cfg)
    bench = spec.load_benchmark()
    out, checks = run.run_cell(
        cell, cfg, mix, spec.cell_metrics(bench, name, "end_to_end"), seed,
        seconds, False, jax.devices()[:cell["chips"]],
        {"hbm_bytes_per_s": 1e11})
    return {c.name: c for c in checks}, out
