"""Distributed (doc-sharded) Seismic vs single-shard reference.

Runs in a subprocess with 8 forced host devices (the main test process
must keep the real single-device view).
"""
from helpers import run_with_devices

CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.data import SyntheticSparseConfig, make_collection
from repro.core import SeismicConfig, SearchParams
from repro.core.distributed import (build_sharded_index, make_distributed_search,
                                    place_on_mesh)
from repro.core.baselines import exact_search
from repro.core.oracle import recall_at_k
from repro.sparse.ops import PaddedSparse

assert len(jax.devices()) == 8
cfg = SyntheticSparseConfig(dim=512, n_docs=1024, n_queries=16, doc_nnz=32,
                            query_nnz=12, n_topics=16, topic_coords=96, seed=3)
docs_np, queries_np, _ = make_collection(cfg)
docs = PaddedSparse(jnp.asarray(docs_np.coords), jnp.asarray(docs_np.vals), docs_np.dim)
queries = PaddedSparse(jnp.asarray(queries_np.coords), jnp.asarray(queries_np.vals), queries_np.dim)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
scfg = SeismicConfig(lam=96, beta=8, alpha=0.4, block_cap=24, summary_nnz=24)
stacked = place_on_mesh(build_sharded_index(docs, scfg, n_shards=4), mesh)
p = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive")
search = make_distributed_search(mesh, p, doc_axes=("model",), data_axis="data")
with jax.set_mesh(mesh):
    s, ids = jax.jit(search)(stacked, queries.coords, queries.vals)
es, eids = exact_search(docs, queries, 10)
recalls = [recall_at_k(np.asarray(ids[q]), np.asarray(eids[q])) for q in range(16)]
assert np.mean(recalls) >= 0.9, np.mean(recalls)

# global ids must be valid and scores exact IPs
q_dense = np.zeros((16, docs.dim))
rows = np.arange(16)[:, None]
np.add.at(q_dense, (rows, queries_np.coords), queries_np.vals)
for q in range(16):
    for j in range(10):
        doc = int(ids[q, j])
        if doc < 0:
            continue
        assert 0 <= doc < docs.n
        ip = (q_dense[q][docs_np.coords[doc]] * docs_np.vals[doc]).sum()
        assert abs(float(s[q, j]) - ip) < 1e-3 * max(1.0, abs(ip)), (q, j)
print("OK distributed")
"""


def test_distributed_search_8dev():
    out = run_with_devices(CODE, n_devices=8)
    assert "OK distributed" in out


PLACEMENT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.data import SyntheticSparseConfig, make_collection
from repro.core import SeismicConfig, SearchParams
from repro.core.distributed import build_sharded_index, shard_views
from repro.serve import ReplicaSeismicServer

devs = jax.devices()
assert len(devs) == 4
cfg = SyntheticSparseConfig(dim=256, n_docs=512, n_queries=8, doc_nnz=24,
                            query_nnz=8, n_topics=8, topic_coords=64, seed=2)
docs_np, queries_np, _ = make_collection(cfg)
scfg = SeismicConfig(lam=64, beta=4, alpha=0.4, block_cap=16, summary_nnz=16)
stacked = build_sharded_index(docs_np, scfg, n_shards=4, list_chunk=16)
for leaf in jax.tree.leaves(stacked):
    assert leaf.shape[0] == 4
    for sh in leaf.addressable_shards:       # shard s on device s, alone
        s = sh.index[0].start or 0
        assert sh.data.shape[0] == 1 and sh.device == devs[s], (s, sh.device)
for s, view in enumerate(shard_views(stacked)):
    for leaf in jax.tree.leaves(view):
        assert leaf.devices() == {devs[s]}
server = ReplicaSeismicServer(stacked, SearchParams(k=5, cut=4,
                              block_budget=8), mode="shard", max_batch=8,
                              query_nnz=8, n_docs=cfg.n_docs)
for rid, (view, _) in enumerate(server._replicas):
    assert {d for x in jax.tree.leaves(view) for d in x.devices()} \
        == {devs[rid]}
with server:
    res = server.search(jax.tree.map(jnp.asarray, queries_np))
assert (res.ids >= 0).all()
print("OK placement")
"""


def test_one_shard_per_device_4dev():
    """build_sharded_index builds shard s on device s, and the shard-mode
    replica keeps it there."""
    out = run_with_devices(PLACEMENT, n_devices=4)
    assert "OK placement" in out


KERNELS = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.data import SyntheticSparseConfig, make_collection
from repro.core import SeismicConfig, SearchParams
from repro.core.distributed import (build_sharded_index, make_distributed_search,
                                    place_on_mesh)
from repro.sparse.ops import PaddedSparse

assert len(jax.devices()) == 4
cfg = SyntheticSparseConfig(dim=512, n_docs=1024, n_queries=8, doc_nnz=32,
                            query_nnz=12, n_topics=16, topic_coords=96, seed=4)
docs_np, queries_np, _ = make_collection(cfg)
docs = PaddedSparse(jnp.asarray(docs_np.coords), jnp.asarray(docs_np.vals), docs_np.dim)
mesh = jax.make_mesh((1, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
scfg = SeismicConfig(lam=96, beta=8, alpha=0.4, block_cap=24, summary_nnz=24)
stacked = place_on_mesh(build_sharded_index(docs, scfg, n_shards=4), mesh)
qc, qv = jnp.asarray(queries_np.coords), jnp.asarray(queries_np.vals)
out = {}
for use_kernel in (False, True):
    p = SearchParams(k=10, cut=8, block_budget=32, policy="adaptive",
                     use_kernel=use_kernel)
    search = make_distributed_search(mesh, p, doc_axes=("model",),
                                     data_axis="data")
    with jax.set_mesh(mesh):
        text = str(jax.make_jaxpr(search)(stacked, qc, qv))
        out[use_kernel] = jax.jit(search)(stacked, qc, qv)
    # the kernels sit inside the shard_map body, and only on request
    assert ("summary_dot_batch" in text) == use_kernel, use_kernel
    assert ("gather_dot_batch" in text) == use_kernel, use_kernel
    assert "shard_map" in text
np.testing.assert_array_equal(np.asarray(out[True][1]), np.asarray(out[False][1]))
np.testing.assert_allclose(np.asarray(out[True][0]), np.asarray(out[False][0]),
                           rtol=1e-6)
print("OK kernels under shard_map")
"""


def test_distributed_search_traces_kernels_4dev():
    """The doc-sharded search traces and runs with the pair-match
    kernels (interpret mode) inside ``shard_map`` and answers as the
    gather does."""
    out = run_with_devices(KERNELS, n_devices=4)
    assert "OK kernels under shard_map" in out
