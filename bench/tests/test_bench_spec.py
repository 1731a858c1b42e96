"""BENCHMARK.json and the files it names: allowed names and units,
and every configuration, traffic mix and metric found by name."""
import json
import os
import shutil

import pytest

from benchtest_util import BENCH, ROOT, check_layouts, spec

BENCHMARK = spec.load_benchmark()


def test_names_and_units_use_only_the_allowed_characters():
    assert spec.check_names(BENCHMARK) == []


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "µs", "-x" * 40])
def test_check_names_refuses_a_bad_name(bad):
    b = json.loads(json.dumps(BENCHMARK))
    b["workloads"][0]["name"] = bad
    assert bad in spec.check_names(b)


def test_check_names_refuses_a_bad_unit():
    b = json.loads(json.dumps(BENCHMARK))
    b["end_to_end"][0]["unit"] = "queries per second"
    assert "queries per second" in spec.check_names(b)


def finds_every_part_by_name(bench, root=ROOT, bench_dir=BENCH):
    """Each cell's configuration file and traffic mix, found by name
    under ``root`` and ``bench_dir``."""
    for w in bench["workloads"]:
        cfg = spec.load_config(bench, w["config"], root)
        assert cfg["name"] == w["config"]
        mix = spec.load_traffic(w["traffic"], bench_dir)
        assert mix["api"] in ("submit", "search")
        entry = spec.config_entry(bench, w["config"])
        assert entry["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(root, entry["file"]))


def reports_enough(bench, bench_dir=BENCH):
    """Every metric a cell reports has a reader under ``bench_dir``, and
    every cell reports ``setup_s``, another end-to-end metric and a
    per-layer metric that moves one of its end-to-end metrics."""
    for w in bench["workloads"]:
        e2e = spec.cell_metrics(bench, w["name"], "end_to_end")
        per = spec.cell_metrics(bench, w["name"], "per_layer")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert per
        e2e_names = {m["name"] for m in e2e}
        for m in e2e + per:
            assert callable(spec.metric_reader(m["name"], bench_dir))
        for m in per:
            assert m["moves"] in e2e_names


def test_every_cell_finds_its_config_and_traffic_by_name():
    finds_every_part_by_name(BENCHMARK)


def test_every_metric_has_a_reader_and_every_cell_reports_enough():
    reports_enough(BENCHMARK)


def test_whole_benchmark_checks_accept_a_sharded_configuration(tmp_path):
    """A copy of the benchmark with one more configuration, the r90 one
    split over four chips (``"layout": {"shards": 4}``), and a four-chip
    ``online`` cell of it, listed under the metrics a sharded run reads
    (PERF.md, section 3): every per-configuration and per-cell check of
    the benchmark's tests holds, so a configuration like it can be added
    with files and entries alone."""
    bench = json.loads(json.dumps(BENCHMARK))
    bench_dir = tmp_path / "bench"
    for part in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, part), bench_dir / part)
    cfg = spec.load_config(bench, "splade-msmarco-1m-r90")
    cfg["name"] = "splade-msmarco-4m-r90-shard4"
    cfg["corpus"]["n_docs"] = 4 * cfg["corpus"]["n_docs"]
    cfg["layout"] = {"shards": 4}
    (bench_dir / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    bench["configs"].append(dict(
        spec.config_entry(bench, "splade-msmarco-1m-r90"), name=cfg["name"],
        file=f"bench/configs/{cfg['name']}.json"))
    cell = {"name": "splade-r90-shard4.online", "config": cfg["name"],
            "traffic": "online", "chips": 4,
            "why": "four shards of the corpus behind one online front end"}
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("p50_ms", "p95_ms", "queue_wait_ms.online",
                         "batch_occupancy.online", "batch_wait_ms.online",
                         "idle_share.online"):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = spec.load_benchmark(str(tmp_path))
    assert spec.check_names(bench) == []
    finds_every_part_by_name(bench, str(tmp_path), str(bench_dir))
    reports_enough(bench, str(bench_dir))
    check_layouts(bench, str(tmp_path), str(bench_dir))
    assert spec.shards(spec.load_config(bench, cfg["name"],
                                        str(tmp_path))) == 4
    bench["workloads"][-1]["chips"] = 1
    with pytest.raises(spec.SpecError, match="must equal the cell's chips"):
        check_layouts(bench, str(tmp_path), str(bench_dir))


def test_an_unknown_name_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.workload(BENCHMARK, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no-such-metric")


def test_a_new_metric_file_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "extra.cell.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    assert spec.metric_reader("extra.cell", str(tmp_path))({"x": 4}) == 8


def test_no_cell_pins_the_search_path_and_every_cell_holds_the_cache_off():
    for w in BENCHMARK["workloads"]:
        cfg = spec.load_config(BENCHMARK, w["config"])
        assert set(cfg["search"]) == {"k", "cut", "block_budget"}
        mix = spec.load_traffic(w["traffic"])
        assert mix["server"].get("cache_size", 0) == 0


def test_paths_hold_the_benchmark():
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert os.path.isdir(BENCH)
