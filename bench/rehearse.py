#!/usr/bin/env python3
"""Compile each cell's served search program for a described TPU v5e,
at the cell's real widths, without a chip, and print what the compiler
says it needs.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--workload <name> ...]

For every launch width the cell's server compiles (the online ladder,
or the bulk server's one ``max_batch``), it lowers the program's
``search_pipeline`` with the index passed as shapes and prints
``memory_analysis()``, the index's resident bytes, and their sum
against the chip's 16 GiB. A width that does not fit is reported as
such; the script exits non-zero when any does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from lib import spec  # noqa: E402

HBM_BYTES = 16 * 2 ** 30
DEFAULT_LADDER = (8, 32, 128)     # AsyncSeismicServer.DEFAULT_WIDTHS


def widths(cfg: dict, mix: dict) -> list[int]:
    top = cfg["serve"]["max_batch"]
    if mix["api"] == "search":
        return [top]
    ladder = mix["server"].get("launch_widths") or DEFAULT_LADDER
    return sorted({w for w in ladder if w < top} | {top})


def rehearse(cell: dict, cfg: dict, mix: dict, one_chip) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from repro.core import SeismicConfig
    from repro.core.build import index_shape
    from repro.retrieval import SearchParams, search_pipeline
    from repro.sparse.ops import PaddedSparse
    c, s = cfg["corpus"], cfg["search"]
    index = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        index_shape(c["n_docs"], c["dim"], c["doc_nnz"],
                    SeismicConfig(**cfg["index"])))
    resident = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(index))
    params = SearchParams(**s)
    nnz = cfg["serve"]["query_nnz"]
    rows = []
    for w in widths(cfg, mix):
        q = PaddedSparse(
            jax.ShapeDtypeStruct((w, nnz), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((w, nnz), jnp.float32, sharding=one_chip),
            c["dim"])
        m = search_pipeline.lower(index, q, params).compile() \
            .memory_analysis()
        need = resident + m.temp_size_in_bytes + m.output_size_in_bytes
        rows.append(dict(workload=cell["name"], width=w,
                         block_budget=s["block_budget"],
                         resident_bytes=resident,
                         temp_bytes=m.temp_size_in_bytes,
                         argument_bytes=m.argument_size_in_bytes,
                         output_bytes=m.output_size_in_bytes,
                         need_bytes=need, fits=need <= HBM_BYTES))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import runtime
    jax.config.update("jax_enable_compilation_cache", False)
    runtime.on_tpu = lambda: True       # kernels lower through Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = spec.load_benchmark()
    names = args.workload or [w["name"] for w in bench["workloads"]]
    ok = True
    for name in names:
        cell = spec.workload(bench, name)
        for row in rehearse(cell, spec.load_config(bench, cell["config"]),
                            spec.load_traffic(cell["traffic"]), one_chip):
            print(json.dumps(row), flush=True)
            ok &= row["fits"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
