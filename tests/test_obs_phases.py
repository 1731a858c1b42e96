"""The batcher's host phases (``repro.obs.phase``), the compilations
counter, and the stage scopes of the search program.

Phases are checked two ways: as telemetry histograms (one record per
launch, sums inside the wall time) and as ``seismic.*`` events in a
CPU profiler trace, read back with ``jax.profiler.ProfileData``.
"""
import contextlib
import glob
import os
import re
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graph import build_doc_graph
from repro.retrieval import STAGES, SearchParams, search_pipeline
from repro.retrieval.pipeline import run_pipeline
from repro.serve import AsyncSeismicServer, SeismicServer
from repro.serve.telemetry import ServerTelemetry
from repro.sparse.ops import PaddedSparse

PHASES = ("batch_wait", "pack", "dispatch", "device_wait", "to_host",
          "fulfil")
N_LAUNCHES = 4


def _server(idx, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("query_nnz", 16)
    kw.setdefault("deadline_s", 0.001)
    return AsyncSeismicServer(
        idx, SearchParams(k=5, cut=8, block_budget=8), **kw)


def _serve_one_by_one(srv, small_collection, n=N_LAUNCHES):
    """``n`` requests, each awaited before the next: ``n`` launches."""
    _, queries, *_ = small_collection
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    for i in range(n):
        srv.submit(coords[i], vals[i]).result(30.0)


def _profile(tmp_path):
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _host_lines(profile, prefix="seismic."):
    """[(line, its events named ``prefix*`` in start order)] of every
    host line holding at least one."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = sorted((e for e in line.events
                         if e.name.startswith(prefix)),
                        key=lambda e: e.start_ns)
            if ev:
                out.append((line, ev))
    return out


def test_every_phase_records_once_per_launch(small_index,
                                             small_collection):
    idx, _ = small_index
    srv = _server(idx)
    t0 = time.perf_counter()
    with srv:
        _serve_one_by_one(srv, small_collection)
        tel = srv.telemetry_export()
    wall = time.perf_counter() - t0
    batches = tel["counters"]["batches"]
    assert batches == N_LAUNCHES
    lat = tel["latency_s"]
    for name in PHASES:
        assert lat[name]["count"] == batches, name
    total = sum(lat[n]["mean"] * lat[n]["count"] for n in PHASES)
    assert 0 < total <= wall
    # the launch histogram keeps its interval: dispatch + device_wait
    assert lat["launch"]["count"] == batches
    assert lat["launch"]["mean"] >= lat["dispatch"]["mean"]


def test_phases_are_profiler_events_on_the_batcher_thread(
        small_index, small_collection, tmp_path):
    idx, _ = small_index
    with _server(idx) as srv:
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve_one_by_one(srv, small_collection)
        finally:
            jax.profiler.stop_trace()
    lines = _host_lines(_profile(tmp_path))
    assert len(lines) == 1                  # the batcher thread alone
    line, ev = lines[0]
    names = [e.name[len("seismic."):] for e in ev]
    assert names.count("dispatch") == N_LAUNCHES
    # launch after launch, each phase follows the one before it
    for a, b, na, nb in zip(ev, ev[1:], names, names[1:]):
        assert PHASES.index(nb) == (PHASES.index(na) + 1) % len(PHASES)
        assert b.start_ns >= a.end_ns
    # JAX's own dispatch of the program nests inside seismic.dispatch
    disp = [e for e in ev if e.name == "seismic.dispatch"]
    pjit = [e for e in line.events
            if e.name == "PjitFunction(search_pipeline)"]
    assert len(pjit) >= N_LAUNCHES
    assert all(any(d.start_ns <= p.start_ns and p.end_ns <= d.end_ns
                   for d in disp) for p in pjit)


@pytest.mark.parametrize("with_telemetry", [False, True],
                         ids=["bare", "telemetry"])
def test_bulk_facade_annotates_dispatch_and_to_host(
        small_index, small_collection, tmp_path, with_telemetry):
    idx, _ = small_index
    _, queries, *_ = small_collection
    tel = ServerTelemetry() if with_telemetry else None
    srv = SeismicServer(idx, SearchParams(k=5, cut=8, block_budget=8),
                        max_batch=4, telemetry=tel)
    srv.search(queries)                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.search(queries)
    finally:
        jax.profiler.stop_trace()
    n_chunks = -(-queries.coords.shape[0] // 4)
    ((_, ev),) = _host_lines(_profile(tmp_path))
    assert [e.name for e in ev] == \
        ["seismic.dispatch"] * n_chunks + ["seismic.to_host"]
    if with_telemetry:
        lat = tel.export()["latency_s"]
        assert lat["dispatch"]["count"] == 2 * n_chunks
        assert lat["to_host"]["count"] == 2


def test_compilations_count_only_after_warmup(small_index,
                                              small_collection):
    idx, _ = small_index
    with _server(idx, launch_widths=(2,)) as srv:
        _serve_one_by_one(srv, small_collection)
        assert srv.telemetry_export()["counters"]["compilations"] == 0
        # a width the ladder never warmed compiles on first use; the
        # persistent cache is off so the compile cannot be a disk hit
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            zeros = jnp.zeros((3, 16), jnp.int32)
            jax.block_until_ready(search_pipeline(
                idx, PaddedSparse(zeros, zeros.astype(jnp.float32),
                                  idx.dim),
                SearchParams(k=3, cut=7, block_budget=5)))
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
        assert srv.telemetry_export()["counters"]["compilations"] > 0


def _compiled_text(index, queries, p):
    # a fresh function object each time, so no trace is reused
    return jax.jit(lambda i, c, v: run_pipeline(i, c, v, p)).lower(
        index, queries.coords, queries.vals).compile().as_text()


def _without_metadata(text: str) -> str:
    """HLO text less op metadata and the source-location tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(l for l in text.splitlines()
                     if not re.match(r"^\d+ ", l))


def test_every_stage_scope_reaches_the_compiled_program(
        small_index, small_collection, monkeypatch):
    idx, _ = small_index
    _, queries, *_ = small_collection
    index = build_doc_graph(idx, degree=4, batch=256)
    p = SearchParams(k=5, cut=8, block_budget=8, graph_degree=4,
                     refine_rounds=1)
    text = _compiled_text(index, queries, p)
    scopes = {m.split("/")[1] for m in re.findall(r'op_name="([^"]*)"',
                                                   text) if "/" in m}
    assert set(STAGES) <= scopes
    # the scopes add metadata and nothing else
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(index, queries, p)
    assert not set(STAGES) & {
        m.split("/")[1] for m in re.findall(r'op_name="([^"]*)"', bare)
        if "/" in m}
    assert _without_metadata(text) == _without_metadata(bare)
