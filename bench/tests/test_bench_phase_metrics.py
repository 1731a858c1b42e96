"""The front end's phase metrics, ``batch_wait_ms.online`` and
``host_ms_per_launch.online``, on hand-made telemetry and on a served
batch's own export; both read nothing where the program records no
phases, as a program without them does."""
import numpy as np
import pytest

from benchtest_util import spec

BATCH_WAIT = spec.metric_reader("batch_wait_ms.online")
HOST = spec.metric_reader("host_ms_per_launch.online")


def _hist(total_s, count):
    return {"count": count, "mean": total_s / count if count else 0.0,
            "p50": 0.0, "p95": 0.0, "p99": 0.0, "min": 0.0, "max": 0.0}


def _telemetry(batches=4, phases=True):
    lat = {"queue_wait": _hist(0.2, 8), "launch": _hist(0.4, batches),
           "request_e2e": _hist(1.0, 8)}
    if phases:
        lat.update(batch_wait=_hist(0.03, batches), pack=_hist(0.001, batches),
                   dispatch=_hist(0.002, batches),
                   device_wait=_hist(0.39, batches),
                   to_host=_hist(0.0005, batches),
                   fulfil=_hist(0.0015, batches))
    return {"counters": {"batches": batches, "dispatched": 8,
                         "launch_width_8": batches},
            "latency_s": lat}


def test_sums_over_launches_in_ms():
    run = {"telemetry": _telemetry()}
    assert BATCH_WAIT(run) == pytest.approx(0.03 / 4 * 1e3)
    assert HOST(run) == pytest.approx((0.001 + 0.002 + 0.0005 + 0.0015)
                                      / 4 * 1e3)


@pytest.mark.parametrize("telemetry", [
    None, _telemetry(phases=False), _telemetry(batches=0)],
    ids=["bulk", "no-phases", "no-launch"])
def test_nothing_to_read_is_none(telemetry):
    run = {"telemetry": telemetry}
    assert BATCH_WAIT(run) is None
    assert HOST(run) is None


def test_host_needs_every_host_phase():
    tel = _telemetry()
    del tel["latency_s"]["to_host"]
    assert HOST({"telemetry": tel}) is None
    assert BATCH_WAIT({"telemetry": tel}) is not None


def test_a_served_batch_exports_what_the_readers_read(small_cell_server):
    srv, coords, vals = small_cell_server
    for i in range(3):
        srv.submit(coords[i], vals[i]).result(30.0)
    tel = srv.telemetry_export()
    run = {"telemetry": tel}
    assert tel["counters"]["batches"] == 3
    wait = tel["latency_s"]["batch_wait"]
    assert BATCH_WAIT(run) == pytest.approx(
        wait["mean"] * wait["count"] / 3 * 1e3)
    assert BATCH_WAIT(run) > 0 and HOST(run) > 0


@pytest.fixture
def small_cell_server():
    import jax.numpy as jnp
    from repro.core import SeismicConfig, build_index
    from repro.data import SyntheticSparseConfig, make_collection
    from repro.retrieval import SearchParams
    from repro.serve import AsyncSeismicServer
    from repro.sparse.ops import PaddedSparse
    docs_np, q_np, _ = make_collection(SyntheticSparseConfig(
        dim=512, n_docs=1024, n_queries=4, doc_nnz=32, query_nnz=16,
        n_topics=16, topic_coords=64, seed=3))
    docs = PaddedSparse(jnp.asarray(docs_np.coords),
                        jnp.asarray(docs_np.vals), docs_np.dim)
    index = build_index(docs, SeismicConfig(lam=64, beta=8, alpha=0.4,
                                            block_cap=16, summary_nnz=16),
                        list_chunk=16)
    srv = AsyncSeismicServer(index, SearchParams(k=5, cut=8, block_budget=8),
                             max_batch=8, query_nnz=16, deadline_s=0.001)
    with srv:
        yield srv, np.asarray(q_np.coords), np.asarray(q_np.vals)
