"""A configuration that shards its corpus (``"layout": {"shards": N}``):
the spec's rules, the server it gets, and, in a subprocess on four
virtual CPU devices, a cut-down sharded cell end to end: one shard
built on each device, served through the replica server's shard mode,
checked against the ranged reference over the whole corpus."""
import json
import os
import subprocess
import sys

import pytest

from benchtest_util import (ROOT, load_run, run_tiny, shard_layout,
                            spec, tiny_cell)

HERE = os.path.dirname(os.path.abspath(__file__))

# Runs on four virtual devices; prints one JSON object of readings.
FOUR_DEVICES = r'''
import json, sys
sys.path.insert(0, HERE)
import numpy as np
import jax
from benchtest_util import load_run, run_tiny, shard_layout
from lib import reference, workbytes

assert len(jax.devices()) == 4, jax.devices()
run = load_run()
out = {}

orig_build = run.build_sharded
def build_sharded(cfg, *a):
    index = orig_build(cfg, *a)
    one = jax.tree.map(lambda x: x[0], index)
    per_shard = [np.asarray((index.block_len[s] > 0).sum(axis=-1))
                 for s in range(index.block_len.shape[0])]
    out["build"] = dict(
        devices=sorted(d.id for d in index.block_len.sharding.device_set),
        shards=int(index.block_len.shape[0]),
        row_bytes=[workbytes.summary_row_bytes(index),
                   workbytes.forward_row_bytes(index)],
        one_shard_row_bytes=[workbytes.summary_row_bytes(one),
                             workbytes.forward_row_bytes(one)],
        live_summed=np.sum(per_shard, axis=0).tolist())
    return index
run.build_sharded = build_sharded

orig_live = workbytes.live_blocks_probed
def live_blocks_probed(live_per_list, probed):
    out.setdefault("live_per_list", np.asarray(live_per_list).tolist())
    return orig_live(live_per_list, probed)
workbytes.live_blocks_probed = live_blocks_probed

from repro.serve import replica
orig_search = replica.search_shard
calls = {}
def search_shard(view, q_coords, q_vals, shard_offset, p, n_docs):
    dev = next(iter(view.block_len.devices())).id
    calls[dev] = calls.get(dev, 0) + 1
    return orig_search(view, q_coords, q_vals, shard_offset, p, n_docs)
replica.search_shard = search_shard

def summary(checks, res):
    return dict(correct=res["correct"], attempted=res["attempted"],
                failed=res["failed"], count=res["device"]["count"],
                metrics=sorted(res["metrics"]),
                checks={n: [c.value, c.limit, c.ok]
                        for n, c in checks.items()})

out["program"] = summary(*run_tiny("splade-r90.online",
                                   cfg_edit=shard_layout(4), chips=4))
out["shard_calls"] = {str(k): v for k, v in sorted(calls.items())}

def altered(view, q_coords, q_vals, shard_offset, p, n_docs):
    s, g, e = orig_search(view, q_coords, q_vals, shard_offset, p, n_docs)
    if shard_offset == 0:
        g = g.at[0, 0].set((g[0, 0] + 1) % n_docs)
    return s, g, e
replica.search_shard = altered
out["altered"] = summary(*run_tiny("splade-r90.online",
                                   cfg_edit=shard_layout(4), chips=4))

# the ranged reference on four devices against the one-device scan
rng = np.random.default_rng(7)
cases = {}
for name, n in (("divisible", 4096), ("ragged", 4097), ("ties", 103)):
    coords = np.stack([rng.choice(256, 16, replace=False)
                       for _ in range(n)]).astype(np.int32)
    vals = rng.random((n, 16), dtype=np.float32)
    if name == "ties":
        # docs 20..40 are one document: ranges break at 25, 51 and 77,
        # so a top 10 of that document crosses the first boundary
        coords[20:41], vals[20:41] = coords[20], vals[20] * 0 + 4.0
    q = rng.random((12, 256), dtype=np.float32)
    one_s, one_i = reference.exact_topk(coords, vals, q, 10,
                                        device=jax.devices()[0])
    rs, ri = reference.exact_topk_ranged(coords, vals, q, 10,
                                         devices=jax.devices())
    cases[name] = dict(ids_equal=bool((one_i == ri).all()),
                       scores_equal=bool((one_s == rs).all()),
                       dtypes=[str(ri.dtype), str(rs.dtype)],
                       first_ids=ri[0].tolist())
out["reference"] = cases
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c", f"HERE = {HERE!r}\n" + FOUR_DEVICES],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_program_is_correct(four):
    r = four["program"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0 and r["count"] == 4
    assert set(r["metrics"]) >= {"recall_at_10", "setup_s", "p95_ms"}


def test_every_shard_serves_every_launch(four):
    calls = four["shard_calls"]
    assert len(calls) == 4 and len(set(calls.values())) == 1
    assert list(calls.values())[0] > 0


def test_one_shard_is_built_on_each_device(four):
    assert four["build"]["shards"] == 4
    assert four["build"]["devices"] == [0, 1, 2, 3]


def test_row_bytes_of_the_stacked_index_are_one_shards(four):
    assert four["build"]["row_bytes"] == four["build"]["one_shard_row_bytes"]


def test_live_blocks_are_summed_over_the_shards(four):
    assert four["live_per_list"] == four["build"]["live_summed"]
    assert sum(four["live_per_list"]) > 0


def test_an_answer_altered_in_one_shard_is_not_correct(four):
    """Shard 0's best id of each launch's first query, one higher: its
    served score is not its own (``score_gap``), or it repeats an id of
    the answer (``bad_answers``)."""
    r = four["altered"]
    assert not r["correct"]
    assert not (r["checks"]["score_gap"][2] and r["checks"]["bad_answers"][2])


@pytest.mark.parametrize("case", ["divisible", "ragged", "ties"])
def test_ranged_reference_equals_the_one_device_scan(four, case):
    r = four["reference"][case]
    assert r["ids_equal"] and r["scores_equal"]
    assert r["dtypes"] == ["int32", "float32"]
    if case == "ties":
        assert r["first_ids"] == list(range(20, 30))


def test_spec_refuses_shards_other_than_the_cells_chips():
    cell, cfg, mix = tiny_cell("splade-r90.online")
    shard_layout(4)(cfg)
    with pytest.raises(spec.SpecError, match="must equal the cell's chips"):
        spec.check_layout(cell, cfg, mix)
    with pytest.raises(spec.SpecError, match="must equal the cell's chips"):
        run_tiny("splade-r90.online", cfg_edit=shard_layout(4))
    spec.check_layout(dict(cell, chips=4), cfg, mix)


def test_spec_refuses_a_sharded_search_mix():
    cell, cfg, mix = tiny_cell("splade-r97.bulk")
    shard_layout(4)(cfg)
    with pytest.raises(spec.SpecError, match="only through submit"):
        spec.check_layout(dict(cell, chips=4), cfg, mix)
    with pytest.raises(spec.SpecError, match="only through submit"):
        run_tiny("splade-r97.bulk", cfg_edit=shard_layout(4), chips=4)


@pytest.mark.parametrize("layout", [{"shards": 0}, {"shards": 2.0},
                                    {"shard": 4}, 4])
def test_spec_refuses_a_malformed_layout(layout):
    with pytest.raises(spec.SpecError, match="layout.shards"):
        spec.shards({"name": "x", "layout": layout})


def test_a_configuration_without_layout_is_not_sharded():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert spec.shards(spec.load_config(bench, c["name"])) == 0


class _Recorder:
    def __init__(self, index, params, **kw):
        self.index, self.params, self.kw = index, params, kw


@pytest.fixture
def recorded(monkeypatch):
    import repro.serve as serve
    made = {}
    for name in ("SeismicServer", "AsyncSeismicServer",
                 "ReplicaSeismicServer"):
        cls = type(name, (_Recorder,), {})
        monkeypatch.setattr(serve, name, cls)
        made[name] = cls
    return made


def _params(cfg):
    from repro.retrieval import SearchParams
    s = cfg["search"]
    return SearchParams(k=s["k"], cut=s["cut"], block_budget=s["block_budget"])


@pytest.mark.parametrize("name", ["splade-r90.online", "splade-r97.bulk"])
def test_make_server_gives_an_unsharded_cell_the_same_server(name,
                                                             recorded):
    """The server class, search parameters and arguments a configuration
    without ``layout`` got before it could shard."""
    bench = spec.load_benchmark()
    cell = spec.workload(bench, name)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    srv = load_run().make_server("index", cfg, mix)
    assert srv.index == "index" and srv.params == _params(cfg)
    if mix["api"] == "search":
        assert type(srv).__name__ == "SeismicServer"
        assert srv.kw == {"max_batch": cfg["serve"]["max_batch"]}
        return
    assert type(srv).__name__ == "AsyncSeismicServer"
    s = mix["server"]
    assert srv.kw == dict(
        max_batch=cfg["serve"]["max_batch"],
        query_nnz=cfg["serve"]["query_nnz"], launch_widths=None,
        deadline_s=s["deadline_ms"] * 1e-3, queue_bound=s["queue_bound"],
        cache_size=s["cache_size"], coalesce=s["coalesce"])


def test_make_server_serves_a_sharded_corpus_in_shard_mode(recorded):
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "splade-msmarco-1m-r90")
    shard_layout(4)(cfg)
    mix = spec.load_traffic("online")
    srv = load_run().make_server("stacked", cfg, mix)
    assert type(srv).__name__ == "ReplicaSeismicServer"
    assert srv.params == _params(cfg)
    assert srv.kw.pop("mode") == "shard"
    assert srv.kw.pop("n_docs") == cfg["corpus"]["n_docs"]
    unsharded = load_run().make_server("stacked",
                                       spec.load_config(bench, cfg["name"]),
                                       mix)
    assert srv.kw == unsharded.kw


def test_make_server_passes_every_search_field(recorded):
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, "splade-msmarco-1m-r90")
    cfg["search"].update(superblock_fanout=8, graph_degree=4,
                         refine_rounds=1)
    srv = load_run().make_server("index", cfg, spec.load_traffic("online"))
    assert (srv.params.superblock_fanout, srv.params.graph_degree,
            srv.params.refine_rounds) == (8, 4, 1)
    assert srv.params.block_budget == cfg["search"]["block_budget"]
