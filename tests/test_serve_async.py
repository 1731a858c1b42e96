"""Async serving subsystem: deadline micro-batching, admission control,
result cache, telemetry, and parity with the raw jitted pipeline."""
import struct
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.retrieval import SearchParams, search_pipeline
from repro.serve import (AsyncSeismicServer, LRUCache, RequestQueue,
                         ServerTelemetry, query_fingerprint)
from repro.serve.queue import Request, ServeFuture
from repro.serve.telemetry import Histogram


def _params(**kw):
    kw.setdefault("k", 5)
    kw.setdefault("cut", 8)
    kw.setdefault("block_budget", 8)
    return SearchParams(**kw)


def _server(small_index, **kw):
    idx, _ = small_index
    kw.setdefault("max_batch", 8)
    kw.setdefault("query_nnz", 16)
    kw.setdefault("deadline_s", 0.05)
    return AsyncSeismicServer(idx, _params(), **kw)


# ---------------------------------------------------------- dispatch

def test_deadline_expiry_partial_launch(small_index, small_collection):
    """Fewer than max_batch queries must still launch (padded) once the
    dispatch deadline expires — the acceptance-criterion behavior."""
    _, queries, *_ = small_collection
    srv = _server(small_index, deadline_s=0.08)
    with srv:
        t0 = time.monotonic()
        futs = [srv.submit(np.asarray(queries.coords[i]),
                           np.asarray(queries.vals[i]))
                for i in range(3)]                    # 3 < max_batch=8
        for f in futs:
            assert f.wait(5.0)
        waited = time.monotonic() - t0
    res = [f.result() for f in futs]
    # one partial (padded) launch served all three requests
    assert all(r.occupancy == 3 for r in res)
    assert waited >= 0.08          # not dispatched before the deadline
    assert waited < 4.0
    tel = srv.telemetry_export()
    assert tel["batch"]["occupancy_counts"] == {"3": 1}


def test_batch_full_dispatch_beats_deadline(small_index, small_collection):
    """A full batch launches immediately, long before a lazy deadline."""
    _, queries, *_ = small_collection
    srv = _server(small_index, deadline_s=30.0)       # effectively never
    with srv:
        t0 = time.monotonic()
        futs = [srv.submit(np.asarray(queries.coords[i % queries.n]),
                           np.asarray(queries.vals[i % queries.n]))
                for i in range(8)]                    # == max_batch
        for f in futs:
            assert f.wait(10.0)
        waited = time.monotonic() - t0
    assert waited < 10.0                              # not the deadline
    assert all(f.result().occupancy == 8 for f in futs)


def test_async_matches_unbatched_pipeline(small_index, small_collection):
    """Micro-batched results == one direct pipeline call per shape."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    p = _params()
    want_s, want_ids, want_ev = search_pipeline(idx, queries, p)
    srv = _server(small_index)
    with srv:
        res = srv.search(queries)
    np.testing.assert_array_equal(res.ids, np.asarray(want_ids))
    np.testing.assert_allclose(res.scores, np.asarray(want_s),
                               rtol=1e-6)
    np.testing.assert_array_equal(res.docs_evaluated,
                                  np.asarray(want_ev))


def test_queries_wider_than_nnz_budget(small_index, small_collection):
    """Overlong queries keep their heaviest coordinates and still serve."""
    _, queries, *_ = small_collection
    c = np.concatenate([np.asarray(queries.coords[0])] * 3)
    v = np.concatenate([np.asarray(queries.vals[0]),
                        np.zeros((2 * queries.nnz_max,), np.float32)])
    srv = _server(small_index)
    with srv:
        fut = srv.submit(c, v, deadline_s=0.01)
        res = fut.result(5.0)
    assert res.ids.shape == (5,)
    assert (res.ids >= -1).all()


# ---------------------------------------------- launch-width ladder

def test_launch_width_ladder_defaults(small_index):
    """Default rungs are (8, 32, 128) clipped to max_batch, which is
    always the top rung."""
    idx, _ = small_index
    mk = lambda mb: AsyncSeismicServer(idx, _params(), max_batch=mb)
    assert mk(32).launch_widths == (8, 32)
    assert mk(8).launch_widths == (8,)
    assert mk(200).launch_widths == (8, 32, 128, 200)
    assert mk(3).launch_widths == (3,)


def test_launch_width_explicit_and_validation(small_index):
    idx, _ = small_index
    srv = AsyncSeismicServer(idx, _params(), max_batch=16,
                             launch_widths=(4, 2, 4))
    assert srv.launch_widths == (2, 4, 16)     # sorted, deduped, top rung
    with pytest.raises(ValueError, match="launch_widths"):
        AsyncSeismicServer(idx, _params(), max_batch=16,
                           launch_widths=(0, 4))
    with pytest.raises(ValueError, match="launch_widths"):
        AsyncSeismicServer(idx, _params(), max_batch=16,
                           launch_widths=(4, 32))


def test_pick_width_smallest_cover(small_index):
    idx, _ = small_index
    srv = AsyncSeismicServer(idx, _params(), max_batch=16,
                             launch_widths=(2, 4))
    assert [srv._pick_width(n) for n in (1, 2, 3, 4, 5, 16)] \
        == [2, 2, 4, 4, 16, 16]


def test_launch_width_dispatch_and_telemetry(small_index,
                                             small_collection):
    """A 3-request batch dispatches at the smallest covering rung (4),
    not max_batch, and the per-width telemetry counter records it —
    results still match the raw pipeline."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    srv = _server(small_index, max_batch=8, launch_widths=(2, 4),
                  deadline_s=0.05)
    with srv:
        futs = [srv.submit(np.asarray(queries.coords[i]),
                           np.asarray(queries.vals[i]))
                for i in range(3)]
        res = [f.result(10.0) for f in futs]
    assert all(r.occupancy == 3 for r in res)
    counters = srv.telemetry_export()["counters"]
    assert counters["launch_width_4"] == 1
    assert "launch_width_8" not in counters
    p = _params()
    want_s, want_ids, _ = search_pipeline(idx, queries, p)
    for i, r in enumerate(res):
        np.testing.assert_array_equal(r.ids, np.asarray(want_ids)[i])
        np.testing.assert_allclose(r.scores, np.asarray(want_s)[i],
                                   rtol=1e-6)


@pytest.mark.parametrize("use_kernel,counted",
                         [(None, "lookup_gather"), (False, "lookup_gather"),
                          (True, "lookup_pairs")],
                         ids=["auto", "gather", "pairs"])
def test_lookup_counter_one_per_launch(small_index, small_collection,
                                       use_kernel, counted):
    """Every launch counts once under the query-weight lookup its
    program was compiled with; on the CPU the default is the gather."""
    idx, _ = small_index
    _, queries, *_ = small_collection
    srv = AsyncSeismicServer(idx, _params(use_kernel=use_kernel),
                             max_batch=4, query_nnz=16, launch_widths=(4,),
                             deadline_s=0.01)
    with srv:
        futs = [srv.submit(np.asarray(queries.coords[i]),
                           np.asarray(queries.vals[i]))
                for i in range(8)]
        for f in futs:
            f.result(30.0)
    counters = srv.telemetry_export()["counters"]
    assert counters["batches"] >= 2
    assert counters[counted] == counters["batches"]
    assert not ({"lookup_pairs", "lookup_gather"} - {counted}) & set(counters)


# -------------------------------------------------- admission control

def test_admission_reject_new(small_index, small_collection):
    _, queries, *_ = small_collection
    srv = _server(small_index, queue_bound=2, admission="reject",
                  max_batch=4, deadline_s=0.2)
    # don't start the worker: the queue must actually fill (distinct
    # queries — identical ones would coalesce instead of queueing)
    futs = [srv.submit(np.asarray(queries.coords[i]),
                       np.asarray(queries.vals[i])) for i in range(4)]
    statuses = [f.status for f in futs]
    assert statuses.count("rejected") == 2
    assert srv.telemetry_export()["counters"]["rejected"] == 2
    srv.queue.close()


def test_admission_shed_oldest(small_index, small_collection):
    _, queries, *_ = small_collection
    srv = _server(small_index, queue_bound=2, admission="shed_oldest",
                  max_batch=4, deadline_s=0.2)
    futs = [srv.submit(np.asarray(queries.coords[i]),
                       np.asarray(queries.vals[i])) for i in range(4)]
    assert futs[0].status == "shed"
    assert futs[1].status == "shed"
    assert futs[2].status == "pending"
    assert futs[3].status == "pending"
    with pytest.raises(RuntimeError, match="shed"):
        futs[0].result(0.0)
    assert srv.telemetry_export()["counters"]["shed"] == 2
    srv.queue.close()


def test_restart_after_stop_raises(small_index):
    """stop() closes the queue for good; a silent dead restart (every
    submit failing 'closed') must be a loud error instead."""
    srv = _server(small_index)
    with srv:
        pass
    with pytest.raises(RuntimeError, match="stopped"):
        srv.start()


def test_stop_drains_pending_requests(small_index, small_collection):
    """close() must serve what was admitted, not strand futures."""
    _, queries, *_ = small_collection
    srv = _server(small_index, deadline_s=60.0)       # deadline never fires
    with srv:
        futs = [srv.submit(np.asarray(queries.coords[i]),
                           np.asarray(queries.vals[i]))
                for i in range(3)]
    # exiting the context closes + drains the queue
    assert all(f.status == "done" for f in futs)


# --------------------------------------------------------------- cache

def test_result_cache_hit(small_index, small_collection):
    _, queries, *_ = small_collection
    srv = _server(small_index, cache_size=32, deadline_s=0.01)
    c = np.asarray(queries.coords[0])
    v = np.asarray(queries.vals[0])
    with srv:
        first = srv.submit(c, v).result(5.0)
        second = srv.submit(c, v).result(5.0)
    assert not first.cached
    assert second.cached
    np.testing.assert_array_equal(first.ids, second.ids)
    tel = srv.telemetry_export()
    assert tel["cache"]["hits"] == 1
    assert tel["cache"]["hit_rate"] == pytest.approx(0.5)
    # the cached row owns its storage: it must not alias the served
    # result (mutation poisoning) nor pin the [max_batch, k] launch
    # arrays via a view
    # cache keys carry the serving-epoch prefix (stale-result fix)
    key = struct.pack("<Q", srv.epoch) \
        + query_fingerprint(*srv._normalize(c, v))
    cached_ids, cached_scores, _ = srv.cache.get(key)
    np.testing.assert_array_equal(cached_ids, first.ids)
    assert not np.shares_memory(cached_ids, first.ids)
    assert cached_ids.base is None and cached_scores.base is None


def test_fingerprint_quantized_and_order_invariant():
    c = np.array([5, 9, 2], np.int64)
    v = np.array([1.0, 0.5, 0.25], np.float32)
    base = query_fingerprint(c, v)
    perm = np.array([2, 0, 1])
    assert query_fingerprint(c[perm], v[perm]) == base
    assert query_fingerprint(c, v * (1 + 1e-4)) == base   # sub-grid jitter
    assert query_fingerprint(c, v[::-1].copy()) != base   # different weights
    assert query_fingerprint(c, v * 4.0) != base          # scale bucket moved
    # padding (val 0) entries don't contribute
    assert query_fingerprint(np.append(c, 0), np.append(v, 0.0)) == base
    assert query_fingerprint(np.array([]), np.array([])) == b"empty"


def test_inflight_coalescing_shares_launch_slot(small_index,
                                                small_collection):
    """Identical-fingerprint requests queued CONCURRENTLY must occupy
    one launch slot: submit duplicates before the worker starts, then
    let one batch serve them all (the LRU cache can't catch these —
    no result exists yet when the duplicates arrive)."""
    _, queries, *_ = small_collection
    srv = _server(small_index, deadline_s=0.01)
    c0 = np.asarray(queries.coords[0])
    v0 = np.asarray(queries.vals[0])
    c1 = np.asarray(queries.coords[1])
    v1 = np.asarray(queries.vals[1])
    futs = [srv.submit(c0, v0), srv.submit(c0, v0), srv.submit(c1, v1),
            srv.submit(c0, v0)]
    # three duplicates of q0 share the first request's slot
    assert srv.queue.depth == 2
    with srv:                              # worker drains the backlog
        res = [f.result(10.0) for f in futs]
    assert not res[0].coalesced and not res[2].coalesced
    assert res[1].coalesced and res[3].coalesced
    np.testing.assert_array_equal(res[0].ids, res[1].ids)
    np.testing.assert_array_equal(res[0].ids, res[3].ids)
    np.testing.assert_array_equal(res[0].scores, res[1].scores)
    # followers own their storage (no aliasing with the primary's view)
    assert not np.shares_memory(res[0].ids, res[1].ids)
    tel = srv.telemetry_export()
    assert tel["counters"]["coalesced"] == 2
    assert tel["counters"]["served"] == 4  # all four requests fulfilled
    assert tel["batch"]["occupancy_counts"] == {"2": 1}


def test_inflight_coalescing_retires_after_fulfilment(small_index,
                                                      small_collection):
    """Once a request's slot fulfils, its fingerprint leaves the
    in-flight map: a later duplicate becomes a fresh primary (or a
    cache hit when the LRU is on), never a follower of a dead slot."""
    _, queries, *_ = small_collection
    srv = _server(small_index, deadline_s=0.005)
    c = np.asarray(queries.coords[0])
    v = np.asarray(queries.vals[0])
    with srv:
        first = srv.submit(c, v).result(10.0)
        assert srv._inflight == {}         # retired with the launch
        second = srv.submit(c, v).result(10.0)
    assert not first.coalesced and not second.coalesced
    np.testing.assert_array_equal(first.ids, second.ids)
    assert srv.telemetry_export()["counters"].get("coalesced", 0) == 0


def test_inflight_coalescing_disabled(small_index, small_collection):
    _, queries, *_ = small_collection
    srv = _server(small_index, coalesce=False, deadline_s=0.01)
    c = np.asarray(queries.coords[0])
    v = np.asarray(queries.vals[0])
    f0, f1 = srv.submit(c, v), srv.submit(c, v)
    assert srv.queue.depth == 2            # both occupy real slots
    with srv:
        r0, r1 = f0.result(10.0), f1.result(10.0)
    assert not r0.coalesced and not r1.coalesced
    np.testing.assert_array_equal(r0.ids, r1.ids)


def test_shed_fails_followers(small_index, small_collection):
    """Shedding a primary fails its coalesced followers too — no
    orphaned futures hanging forever."""
    _, queries, *_ = small_collection
    srv = _server(small_index, queue_bound=1, admission="shed_oldest",
                  deadline_s=30.0)
    c0 = np.asarray(queries.coords[0])
    v0 = np.asarray(queries.vals[0])
    c1 = np.asarray(queries.coords[1])
    v1 = np.asarray(queries.vals[1])
    f_primary = srv.submit(c0, v0)
    f_follower = srv.submit(c0, v0)        # coalesces onto f_primary
    # bound=1: sheds f_primary (short deadline so the drain below
    # doesn't wait out the server-default 30s)
    f_new = srv.submit(c1, v1, deadline_s=0.01)
    assert f_primary.status == "shed"
    assert f_follower.status == "shed"
    assert f_new.status == "pending"
    with srv:                              # drain the survivor
        assert f_new.result(10.0).ids.shape == (5,)


def test_lru_cache_eviction():
    cache = LRUCache(2)
    cache.put(b"a", 1)
    cache.put(b"b", 2)
    assert cache.get(b"a") == 1          # refresh a
    cache.put(b"c", 3)                   # evicts b
    assert cache.get(b"b") is None
    assert cache.get(b"a") == 1 and cache.get(b"c") == 3
    stats = cache.stats()
    assert stats["size"] == 2
    assert stats["hits"] == 3 and stats["misses"] == 1


# ----------------------------------------------------- queue mechanics

def _req(deadline, now):
    return Request(coords=np.zeros(4, np.int32),
                   vals=np.zeros(4, np.float32), submit_t=now,
                   deadline=deadline, future=ServeFuture())


def test_queue_next_batch_on_deadline():
    q = RequestQueue(bound=8)
    now = time.monotonic()
    q.put(_req(now + 0.05, now))
    t0 = time.perf_counter()
    batch = q.next_batch(4)
    assert len(batch) == 1
    assert time.perf_counter() - t0 >= 0.045


def test_queue_next_batch_on_full():
    q = RequestQueue(bound=8)
    now = time.monotonic()
    for _ in range(4):
        q.put(_req(now + 60.0, now))
    t0 = time.perf_counter()
    batch = q.next_batch(4)                # full -> no deadline wait
    assert len(batch) == 4
    assert time.perf_counter() - t0 < 1.0


def test_queue_close_unblocks_and_drains():
    q = RequestQueue(bound=8)
    now = time.monotonic()
    q.put(_req(now + 60.0, now))
    got = []
    th = threading.Thread(
        target=lambda: got.extend([q.next_batch(4), q.next_batch(4)]))
    th.start()
    time.sleep(0.02)
    q.close()
    th.join(2.0)
    assert not th.is_alive()
    assert len(got[0]) == 1 and got[1] is None
    status, _ = q.put(_req(now, now))      # closed queue admits nothing
    assert status == "closed"


# ------------------------------------------------- telemetry / staging

def test_histogram_percentiles():
    h = Histogram()
    for ms in range(1, 101):               # 1ms .. 100ms uniform
        h.record(ms * 1e-3)
    s = h.summary()
    assert s["count"] == 100
    assert s["p50"] == pytest.approx(0.050, rel=0.25)
    assert s["p99"] == pytest.approx(0.100, rel=0.25)
    assert s["min"] == pytest.approx(1e-3)
    assert s["max"] == pytest.approx(0.1)


def test_telemetry_export_plain_dict():
    import json
    tel = ServerTelemetry()
    tel.record_latency("launch", 0.01)
    tel.inc("batches")
    tel.observe_occupancy(3)
    tel.observe_queue_depth(5)
    out = tel.export()
    json.dumps(out)                        # plain/serializable
    assert out["counters"]["batches"] == 1
    assert out["batch"]["mean_occupancy"] == 3.0
    assert out["queue"]["depth_max"] == 5
    assert out["latency_s"]["launch"]["count"] == 1


def test_stage_timing_records_all_stages(small_index, small_collection):
    from repro.retrieval import STAGES
    _, queries, *_ = small_collection
    srv = _server(small_index, stage_timing=True, deadline_s=0.01)
    with srv:
        srv.submit(np.asarray(queries.coords[0]),
                   np.asarray(queries.vals[0])).result(10.0)
    lat = srv.telemetry_export()["latency_s"]
    for stage in STAGES:
        assert lat[f"stage_{stage}"]["count"] >= 1
