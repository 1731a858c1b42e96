"""Compile the served path for a described TPU v5e — no chip needed.

The unfused kernels of the main path (``summary_dot_batch``,
``gather_dot_batch`` plain and u8) must lower through Mosaic at MS MARCO
widths, under the stable name their ``pallas_call`` gives them, and
the jitted serving step (``search_pipeline`` at the benchmark's launch
widths, index passed as shapes) must compile for one v5e, take those
kernels by default, and fit its 16 GiB with the whole index resident.

The topology is described inside a module fixture, never at import,
so only the pytest worker that runs this file loads the TPU library.
The compilation cache is off around these compiles: a program compiled
for an absent chip cannot be read back from it.
"""
import os
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.seismic_msmarco import CONFIG
from repro.core.build import index_shape
from repro.kernels import runtime
from repro.kernels.gather_dot.ops import gather_dot_batch
from repro.kernels.summary_dot.ops import summary_dot_batch
from repro.retrieval import SearchParams, search_pipeline
from repro.sparse.ops import PaddedSparse

HBM_BYTES = 16 * 2 ** 30           # one v5e
WIDTH = 256                        # SHAPES["query_online"] launch width
N_DOCS = 1_000_000                 # chip_smoke's corpus
CUT, BUDGET = 10, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """The kernel wrappers pick interpret mode from the attached backend
    (the CPU here); steer them to the Mosaic lowering of the described
    chip."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _queries(sharding, width=WIDTH):
    return PaddedSparse(_spec(sharding, (width, CONFIG.query_nnz), jnp.int32),
                        _spec(sharding, (width, CONFIG.query_nnz),
                              jnp.float32), CONFIG.dim)


def test_summary_dot_lowers_for_v5e(one_chip, mosaic):
    s = CONFIG.index.summary_nnz
    l = CUT * CONFIG.index.n_blocks
    args = (_queries(one_chip),
            _spec(one_chip, (WIDTH, l, s), jnp.int32),
            _spec(one_chip, (WIDTH, l, s), jnp.uint8),
            _spec(one_chip, (WIDTH, l), jnp.float32),
            _spec(one_chip, (WIDTH, l), jnp.float32))
    text = jax.jit(summary_dot_batch).lower(*args).compile().as_text()
    # the kernel's name= names its custom call, which the device trace's
    # op names carry
    assert re.search(r"%summary_dot_batch(\.\d+)? = .*"
                     r'custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "u8"])
def test_gather_dot_lowers_for_v5e(one_chip, mosaic, quant):
    c = BUDGET * CONFIG.index.block_cap
    nnz = CONFIG.doc_nnz
    vals = jnp.uint8 if quant else jnp.bfloat16
    args = [_queries(one_chip),
            _spec(one_chip, (WIDTH, c, nnz), jnp.int32),
            _spec(one_chip, (WIDTH, c, nnz), vals)]
    if quant:
        args += [_spec(one_chip, (WIDTH, c), jnp.float32)] * 2
    text = jax.jit(gather_dot_batch).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "use_kernel,width,budget",
    [(False, WIDTH, BUDGET), (True, WIDTH, BUDGET),
     (None, 256, 96), (None, 8, 64)],
    ids=["xla", "kernel", "default-w256-b96", "default-w8-b64"])
def test_serving_step_fits_one_v5e(one_chip, mosaic, use_kernel, width,
                                   budget):
    """The step at the benchmark cells' shapes (bulk: 256 wide, budget
    96; online: 8 wide, budget 64) takes the pair-match kernels by
    default on the chip, and fits."""
    index = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                         index_shape(N_DOCS, CONFIG.dim, CONFIG.doc_nnz,
                                     CONFIG.index))
    p = SearchParams(k=10, cut=CUT, block_budget=budget,
                     use_kernel=use_kernel, fuse_level=0)
    compiled = search_pipeline.lower(index, _queries(one_chip, width),
                                     p).compile()
    text = compiled.as_text()
    kernels = use_kernel is not False
    assert ("tpu_custom_call" in text) == kernels
    for name in ("summary_dot_batch", "gather_dot_batch"):
        assert (re.search(rf"%{name}(\.\d+)? = ", text) is not None) \
            == kernels, name
    m = compiled.memory_analysis()
    resident = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(index))
    need = resident + m.temp_size_in_bytes + m.output_size_in_bytes
    assert need <= HBM_BYTES, (resident, m)
