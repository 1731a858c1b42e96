"""Observability threaded through serving: request span trees from
real served traffic, coalesced-trace linkage, sampled stage detail,
and the Prometheus endpoint contract — asserted on
the *exported* surface (parsed endpoint text), not registry internals.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

from repro.graph import build_doc_graph
from repro.obs import Observability, start_exporter, validate_trace
from repro.retrieval import STAGES, SearchParams
from repro.serve import AsyncSeismicServer, SeismicServer


def _params(**kw):
    kw.setdefault("k", 5)
    kw.setdefault("cut", 8)
    kw.setdefault("block_budget", 8)
    return SearchParams(**kw)


def _server(idx, obs, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("query_nnz", 16)
    kw.setdefault("deadline_s", 0.05)
    kw.setdefault("params", _params())
    params = kw.pop("params")
    return AsyncSeismicServer(idx, params, obs=obs, **kw)


@pytest.fixture(scope="module")
def graph_index(small_index):
    """The small index carrying a kNN doc graph, so sampled traces get
    refine-round child spans."""
    idx, _ = small_index
    return build_doc_graph(idx, degree=4, batch=256)


def _one_query(small_collection, i=0):
    _, queries, *_ = small_collection
    return (np.asarray(queries.coords[i]), np.asarray(queries.vals[i]))


def _spans_by_name(trace):
    out = {}
    for s in trace.spans:
        out.setdefault(s.name, []).append(s)
    return out


# ------------------------------------------------- span-tree structure

def test_single_request_full_span_tree(graph_index, small_collection):
    """The acceptance criterion: one served request on a sampled launch
    yields a connected request -> queue_wait + launch -> 6 stage spans
    -> refine-round children tree, and the Chrome export validates."""
    obs = Observability.create(stage_sample_every=1)
    srv = _server(graph_index, obs,
                  params=_params(graph_degree=4, refine_rounds=2),
                  deadline_s=0.01)
    c, v = _one_query(small_collection)
    with srv:
        assert srv.submit(c, v).result(10.0).ids.shape == (5,)
    traces = obs.tracer.finished()
    assert len(traces) == 1
    tr = traces[0]
    validate_trace(tr)
    by = _spans_by_name(tr)
    assert tr.root.name == "request"
    assert tr.root.attrs["status"] == "done"
    assert "docs_evaluated" in tr.root.attrs
    # queue_wait and launch hang off the request root
    (qw,), (launch,) = by["queue_wait"], by["launch"]
    assert qw.parent_id == tr.root.span_id
    assert launch.parent_id == tr.root.span_id
    assert launch.attrs["staged"] is True
    assert launch.attrs["occupancy"] == 1
    # all six stages hang off the launch span
    for stage in STAGES:
        (sp,) = by[f"stage_{stage}"]
        assert sp.parent_id == launch.span_id
    # per-round children nest under stage_refine
    (refine,) = by["stage_refine"]
    for j in range(2):
        (rnd,) = by[f"refine_round_{j}"]
        assert rnd.parent_id == refine.span_id
    assert set(by) == ({"request", "queue_wait", "launch",
                        "refine_round_0", "refine_round_1"}
                       | {f"stage_{s}" for s in STAGES})
    # the Chrome export is valid JSON with every span as an event
    chrome = json.loads(json.dumps(obs.tracer.export_chrome()))
    assert len(chrome["traceEvents"]) == len(tr.spans)
    assert {e["ph"] for e in chrome["traceEvents"]} == {"X"}


def test_unsampled_launches_skip_stage_detail(small_index,
                                              small_collection):
    """Off-cadence launches still trace request/queue/launch — stage
    children only appear every ``stage_sample_every``-th launch."""
    idx, _ = small_index
    obs = Observability.create(stage_sample_every=2)
    srv = _server(idx, obs, deadline_s=0.005, coalesce=False)
    c, v = _one_query(small_collection)
    with srv:
        for _ in range(4):                  # 4 sequential solo launches
            srv.submit(c, v).result(10.0)
    traces = obs.tracer.finished()
    assert len(traces) == 4
    staged_flags = []
    for tr in traces:
        validate_trace(tr)
        by = _spans_by_name(tr)
        assert set(by) >= {"request", "queue_wait", "launch"}
        (launch,) = by["launch"]
        staged_flags.append(launch.attrs["staged"])
        assert ("stage_router" in by) == launch.attrs["staged"]
    assert staged_flags == [True, False, True, False]   # seq 0,2 sampled


def test_concurrent_submits_wellformed_trees(small_index,
                                             small_collection):
    """Many threads submitting at once: every finished trace stays
    well-formed, and batch members share a launch interval linked by
    ``batch_seq``."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    obs = Observability.create(stage_sample_every=1)
    srv = _server(idx, obs, coalesce=False, deadline_s=0.01)
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    n_req, results = 16, []
    lock = threading.Lock()

    def client(i):
        r = srv.submit(coords[i % coords.shape[0]],
                       vals[i % vals.shape[0]]).result(20.0)
        with lock:
            results.append(r)

    with srv:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(results) == n_req
    traces = obs.tracer.finished()
    assert len(traces) == n_req
    seqs = {}
    for tr in traces:
        validate_trace(tr)
        by = _spans_by_name(tr)
        (launch,) = by["launch"]
        assert by["queue_wait"][0].parent_id == tr.root.span_id
        seqs.setdefault(launch.attrs["batch_seq"], []).append(launch)
    # batch members agree on the launch interval and occupancy
    for members in seqs.values():
        assert len({(m.t0, m.t1) for m in members}) == 1
        assert all(m.attrs["occupancy"] == len(members)
                   for m in members)
    assert sum(len(m) for m in seqs.values()) == n_req


def test_coalesced_follower_trace_linkage(small_index, small_collection):
    """A coalesced duplicate gets its own complete trace whose root
    carries ``coalesced_into=<primary trace id>``."""
    idx, _ = small_index
    obs = Observability.create()
    srv = _server(idx, obs, deadline_s=0.01)
    c, v = _one_query(small_collection)
    f0 = srv.submit(c, v)                   # queued before worker start
    f1 = srv.submit(c, v)                   # coalesces onto f0's slot
    with srv:
        r0, r1 = f0.result(10.0), f1.result(10.0)
    assert not r0.coalesced and r1.coalesced
    traces = obs.tracer.finished()
    assert len(traces) == 2
    by_link = {tr.root.attrs.get("coalesced_into"): tr for tr in traces}
    primary = by_link.pop(None)
    ((linked_id, follower),) = by_link.items()
    assert linked_id == primary.trace_id
    for tr in (primary, follower):
        validate_trace(tr)
        assert tr.root.attrs["status"] == "done"
        assert set(_spans_by_name(tr)) >= {"request", "queue_wait",
                                           "launch"}


def test_midexecution_coalesce_spans_clamped(small_index,
                                             small_collection,
                                             monkeypatch):
    """Regression: a duplicate attaching while its primary is already
    executing has submit_t *after* the batch's dispatch_t; the follower
    queue_wait/launch spans must be clamped to non-negative intervals
    (pre-fix ``validate_trace`` rejected the inverted queue_wait)."""
    import repro.serve.batcher as batcher_mod
    idx, _ = small_index
    obs = Observability.create(stage_sample_every=0)   # fused path
    srv = _server(idx, obs, deadline_s=0.005)
    c, v = _one_query(small_collection)
    entered, release = threading.Event(), threading.Event()
    real = batcher_mod.search_pipeline
    first = []

    def slow_pipeline(index, q, params):
        out = real(index, q, params)
        if not first:                 # hold the first launch open so a
            first.append(1)           # duplicate can attach mid-flight
            entered.set()
            release.wait(10.0)
        return out

    with srv:                         # start (and warmup) unpatched
        monkeypatch.setattr(batcher_mod, "search_pipeline", slow_pipeline)
        f0 = srv.submit(c, v)
        assert entered.wait(10.0)
        f1 = srv.submit(c, v)         # coalesces onto the running batch
        release.set()
        r0, r1 = f0.result(10.0), f1.result(10.0)
    assert not r0.coalesced and r1.coalesced
    assert r1.latency_s >= 0.0
    traces = obs.tracer.finished()
    assert len(traces) == 2
    follower = next(tr for tr in traces
                    if tr.root.attrs.get("coalesced_into"))
    for tr in traces:
        validate_trace(tr)            # strict: every span has t1 >= t0
    by = _spans_by_name(follower)
    (qw,), (launch,) = by["queue_wait"], by["launch"]
    assert qw.t1 >= qw.t0
    assert launch.t1 >= launch.t0 and follower.root.t1 >= launch.t1


def test_cache_hit_and_rejected_traces_closed(small_index,
                                              small_collection):
    """Non-launch request outcomes still close their traces with a
    status: cache hits at submit, rejects at admission."""
    idx, _ = small_index
    obs = Observability.create()
    srv = _server(idx, obs, cache_size=8, deadline_s=0.005)
    c, v = _one_query(small_collection)
    with srv:
        srv.submit(c, v).result(10.0)
        assert srv.submit(c, v).result(10.0).cached
    statuses = sorted(tr.root.attrs["status"]
                      for tr in obs.tracer.finished())
    assert statuses == ["done", "done"]
    cached = [tr for tr in obs.tracer.finished()
              if tr.root.attrs.get("cached")]
    assert len(cached) == 1
    assert set(_spans_by_name(cached[0])) == {"request"}

    obs2 = Observability.create()
    srv2 = _server(idx, obs2, queue_bound=1, deadline_s=30.0,
                   coalesce=False)
    srv2.submit(c, v)
    f = srv2.submit(*_one_query(small_collection, 1))    # over bound
    assert f.status == "rejected"
    assert [tr.root.attrs["status"] for tr in obs2.tracer.finished()] \
        == ["rejected"]


# --------------------------------------------------- exported metrics

def test_prometheus_endpoint_serving_contract(graph_index,
                                              small_collection):
    """Parse the live /metrics endpoint after traced traffic: per-stage
    latency histograms, serving-health gauges."""
    from repro.obs import parse_prometheus_text

    obs = Observability.create(stage_sample_every=1)
    srv = _server(graph_index, obs,
                  params=_params(graph_degree=4, refine_rounds=1),
                  cache_size=8, deadline_s=0.005)
    _, queries, *_ = small_collection
    coords = np.asarray(queries.coords)
    vals = np.asarray(queries.vals)
    with srv, start_exporter(obs.registry, obs.tracer) as exp:
        for i in range(6):
            srv.submit(coords[i % 3], vals[i % 3]).result(10.0)
        with urllib.request.urlopen(exp.url + "/metrics") as r:
            text = r.read().decode()
    parsed = parse_prometheus_text(text)

    lat = parsed["seismic_latency_seconds"]
    assert lat["type"] == "histogram"

    def count_of(span):
        return lat["samples"].get(
            ("seismic_latency_seconds_count", (("span", span),)), 0.0)

    for span in ("request_e2e", "queue_wait", "launch"):
        assert count_of(span) >= 1
    for stage in STAGES:                    # sampled every launch here
        assert count_of(f"stage_{stage}") >= 1
    assert ("seismic_latency_seconds_bucket" in
            {name for name, _ in lat["samples"]})

    def scalar(name):
        return parsed[name]["samples"][(name, ())]

    assert scalar("seismic_cache_hit_rate") > 0        # repeat queries
    assert scalar("seismic_shed_rate") == 0.0
    assert scalar("seismic_deadline_miss_rate") <= 1.0
    assert scalar("seismic_docs_evaluated_mean") > 0
    occ = list(parsed["seismic_launch_width_occupancy"]
               ["samples"].values())
    assert occ and all(0 < o <= 1 for o in occ)


def test_tuned_drift_gauges(small_index, small_collection):
    """Serving params that match an attached TunedPolicy expose drift
    gauges against the policy's measured cost."""
    from repro.tune.policy import TunedPolicy, attach_tuned

    idx, _ = small_index
    pol = TunedPolicy(target=0.9, k=5, cut=8, block_budget=8,
                      policy="adaptive", measured_recall=0.95,
                      measured_cost=50.0)
    tuned_idx = attach_tuned(idx, [pol])
    obs = Observability.create()
    srv = _server(tuned_idx, obs, deadline_s=0.005)
    assert srv._tuned_match is pol
    with srv:
        srv.submit(*_one_query(small_collection)).result(10.0)
    snap = obs.registry.snapshot()
    (docs,) = snap["seismic_tuned_drift_docs"]["samples"]
    (ratio,) = snap["seismic_tuned_drift_ratio"]["samples"]
    assert docs["labels"] == {"target": "0.9"}
    served_mean = srv._ev_sum / srv._ev_n
    assert docs["value"] == pytest.approx(served_mean - 50.0)
    assert ratio["value"] == pytest.approx(served_mean / 50.0)


def test_telemetry_facade_shares_obs_registry(small_index,
                                              small_collection):
    """With ``obs`` attached the legacy export and the registry are two
    views of the SAME sink — no double bookkeeping."""
    idx, _ = small_index
    obs = Observability.create(stage_sample_every=0, tracing=False)
    srv = _server(idx, obs, deadline_s=0.005)
    assert srv.telemetry.registry is obs.registry
    with srv:
        srv.submit(*_one_query(small_collection)).result(10.0)
    tel = srv.telemetry_export()
    fam = obs.registry.get("seismic_events_total")
    reg_counts = {labels[0]: c.value for labels, c in fam.samples()}
    assert tel["counters"] == reg_counts
    assert reg_counts["served"] == 1


def test_sync_server_sampled_launch_traces(small_index,
                                           small_collection):
    """The offline SeismicServer facade records launch-rooted traces on
    the same sampling cadence."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    obs = Observability.create(stage_sample_every=1)
    srv = SeismicServer(idx, _params(), max_batch=8, obs=obs)
    result = srv.search(queries)            # 16 queries -> 2 launches
    assert result.ids.shape == (queries.n, 5)
    traces = obs.tracer.finished()
    assert len(traces) == 2
    for tr in traces:
        validate_trace(tr)
        assert tr.root.name == "launch"
        assert tr.root.attrs["sync"] is True
        by = _spans_by_name(tr)
        for stage in STAGES:
            (sp,) = by[f"stage_{stage}"]
            assert sp.parent_id == tr.root.span_id
    lat = srv.telemetry.export()["latency_s"]
    for stage in STAGES:
        assert lat[f"stage_{stage}"]["count"] == 2
