"""The harness end to end on the CPU at a cut-down size: it refuses a
CPU platform, the program as configured comes out correct, and the
lower-precision control and a planted fault come out not correct."""
import json
import os
import subprocess
import sys

import pytest

from benchtest_util import BENCH, ROOT, run_tiny

CELLS = ["splade-r90.online", "splade-r97.bulk"]


def test_run_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "splade-r90.online", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("name", CELLS)
def test_program_as_configured_is_correct(name):
    checks, out = run_tiny(name)
    assert out["correct"], [c.line() for c in checks.values()]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"recall_at_10", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(name):
    """The program's own u8 forward plane in place of the configured
    bf16 one: the score gap fails, recall alone would not."""
    def quant(cfg):
        cfg["index"]["fwd_quant"] = True
    checks, out = run_tiny(name, cfg_edit=quant)
    assert not out["correct"]
    assert not checks["score_gap"].ok
    assert checks["recall_at_10"].ok


def _alter_first_answer(orig):
    def search_pipeline(index, queries, p):
        s, i, e = orig(index, queries, p)
        return s, i.at[0, 0].set((i[0, 0] + 1) % index.n_docs), e
    return search_pipeline


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_produced_is_not_correct(name, monkeypatch):
    from repro.serve import batcher, engine
    monkeypatch.setattr(batcher, "search_pipeline",
                        _alter_first_answer(batcher.search_pipeline))
    monkeypatch.setattr(engine, "search_pipeline",
                        _alter_first_answer(engine.search_pipeline))
    checks, out = run_tiny(name)
    assert not out["correct"]
    assert not checks["score_gap"].ok


def test_an_unanswered_request_is_not_correct(monkeypatch):
    from repro.serve import batcher
    from repro.serve.queue import ServeFuture
    orig = batcher.AsyncSeismicServer.submit
    calls = []

    def submit(self, coords, vals, deadline_s=None):
        calls.append(1)
        if len(calls) % 10 == 0:
            f = ServeFuture()
            f._fail("rejected")
            return f
        return orig(self, coords, vals, deadline_s)
    monkeypatch.setattr(batcher.AsyncSeismicServer, "submit", submit)
    checks, out = run_tiny("splade-r90.online")
    assert not out["correct"]
    assert out["failed"] == checks["unanswered"].value > 0
