"""BENCHMARK.json and the files it names: allowed names and units,
and every configuration, traffic mix and metric found by name."""
import json
import os

import pytest

from benchtest_util import BENCH, ROOT, spec

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


def test_every_cell_finds_its_config_and_traffic_by_name():
    for w in BENCHMARK["workloads"]:
        cfg = spec.load_config(BENCHMARK, w["config"])
        assert cfg["name"] == w["config"]
        mix = spec.load_traffic(w["traffic"])
        assert mix["api"] in ("submit", "search")
        entry = spec.config_entry(BENCHMARK, w["config"])
        assert entry["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(ROOT, entry["file"]))


def test_every_metric_has_a_reader_and_every_cell_reports_enough():
    for w in BENCHMARK["workloads"]:
        e2e = spec.cell_metrics(BENCHMARK, w["name"], "end_to_end")
        per = spec.cell_metrics(BENCHMARK, w["name"], "per_layer")
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert per
        e2e_names = {m["name"] for m in e2e}
        for m in e2e + per:
            assert callable(spec.metric_reader(m["name"]))
        for m in per:
            assert m["moves"] in e2e_names


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
