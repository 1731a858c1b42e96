"""The calibration tool: its verdict on whether a rate is sustained, and
its online sweeps driven on the CPU at a cut-down size."""
import importlib.util
import json
import os

import numpy as np
import pytest

from benchtest_util import BENCH, load_run, tiny_cell


def load_calibrate():
    load_run()
    s = importlib.util.spec_from_file_location(
        "bench_calibrate", os.path.join(BENCH, "calibrate.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


# (attempted, seconds, completed /s, p95 first s, p95 last s, sustained)
@pytest.mark.parametrize("row", [
    (401, 10.0, 38.72, 196.9, 836.9, False),   # backlog grows in the window
    (802, 10.0, 67.67, 2724.6, 2773.1, False),  # deep from the first second
    (200, 10.0, 19.60, 190.0, 199.0, True),
    (200, 10.0, 18.00, 190.0, 199.0, False),    # 10% of the rate missing
])
def test_sustained_verdict(row):
    *args, want = row
    assert load_calibrate().sustained(*args) is want


def test_online_sweeps_on_a_cut_down_cell(capsys):
    import jax
    cal = load_calibrate()
    run = load_run()
    _, cfg, mix = tiny_cell("splade-r90.online")
    prep = run.prepare(cfg, mix, 3, jax.devices()[:1])
    ref = run.reference_of(prep, cfg, jax.devices()[:1])
    args = (prep["index"], cfg, mix, prep["q_coords"], prep["q_vals"],
            ref["exact_ids"])
    cal.knee_sweep(*args, [100.0, 200.0], 3, 0.3)
    cal.window_repeats(*args, [0.2, 0.4], 2, 3)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["kind"] for r in rows] == ["knee"] * 2 + ["window"] * 4
    assert [r["rate_qps"] for r in rows[:2]] == [100.0, 200.0]
    assert [r["schedule_seed"] for r in rows[2:]] == [3000, 3001] * 2
    assert [r["seconds"] for r in rows[2:]] == [0.2, 0.2, 0.4, 0.4]
    for r in rows:
        assert r["answered"] == r["attempted"] > 0
        assert np.isfinite(r["p95_ms"]) and r["recall_at_10"] > 0.5
    assert all(isinstance(r["sustained"], bool) for r in rows[:2])
