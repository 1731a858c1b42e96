"""Distributed Seismic: doc-sharded indexes, query fan-out, top-k merge.

Scale-out design (DESIGN.md §4): the corpus is sharded over the mesh's
``model`` (and optionally ``pod``) axes; every shard owns a complete
local Seismic index over its documents. Queries are sharded over
``data``. A query executes its local search on every doc shard, then an
``all_gather`` of the per-shard (score, global_id) top-k over the doc
axes and a vectorized merge produce the global top-k. Per-query
collective volume is O(k * n_doc_shards) — independent of corpus size.

The stacked index (leading axis = doc shard) is a regular pytree, so
``jax.jit`` + ``shard_map`` drive the whole thing; the same function is
what the multi-pod dry-run lowers for the retrieval cells. Each
shard's local search is the shared batch-first staged pipeline
(``repro.retrieval``) — the exact code path of local and served
search.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.build import build_index
from repro.core.types import SeismicConfig
from repro.retrieval import SearchParams, run_pipeline
from repro.sparse.ops import PaddedSparse


def shard_collection(docs: PaddedSparse, n_shards: int) -> PaddedSparse:
    """Pad N to a multiple of n_shards and add a leading shard axis:
    [S, N/S, nnz].

    Pad rows are all-zero docs appended at the tail of the LAST shard;
    every merge seam over per-shard results must mask them out (see
    ``mask_shard_topk``) — an all-zero doc that surfaces as a candidate
    scores exactly 0.0 with an out-of-range global id."""
    n = docs.n
    per = -(-n // n_shards)
    pad = per * n_shards - n
    xp = np if isinstance(docs.coords, np.ndarray) else jnp   # host stays host
    coords = xp.pad(docs.coords, ((0, pad), (0, 0)))
    vals = xp.pad(docs.vals, ((0, pad), (0, 0)))
    return PaddedSparse(coords.reshape(n_shards, per, -1),
                        vals.reshape(n_shards, per, -1), docs.dim)


def mask_shard_topk(scores: jax.Array, ids: jax.Array, fwd: PaddedSparse,
                    shard_offset, n_docs: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Globalize one shard's local top-k and mask pad hits to
    ``(-inf, -1)`` — the invariant every cross-shard merge relies on.

    ``shard_collection`` zero-pads the corpus to a multiple of
    ``n_shards``; a pad row that surfaces as a candidate (k exceeding
    the shard's live hits, index surgery, future mutable-index paths)
    scores exactly 0.0 and would enter the merged global top-k with an
    out-of-range global id. Pad rows are exactly the all-zero forward
    rows, so they are masked from ``fwd`` content (dtype-agnostic:
    holds for the f32 and the u8-quantized plane alike); an explicit
    live-doc bound ``n_docs`` additionally masks any globalized id at
    or past it.

    scores/ids: [Q, kk] local top-k; fwd: the shard's forward plane
    [per_shard, nnz]; returns (scores, global ids) with dead slots at
    (-inf, -1).
    """
    per_shard = fwd.coords.shape[0]
    live_row = (fwd.vals != 0).any(axis=-1)             # [per_shard]
    pad_hit = ~jnp.take(live_row, jnp.clip(ids, 0, per_shard - 1),
                        axis=0)
    gids = ids + shard_offset
    dead = (ids < 0) | pad_hit
    if n_docs is not None:
        dead = dead | (gids >= n_docs)
    scores = jnp.where(dead, -jnp.inf, scores)
    gids = jnp.where(dead, -1, gids)
    return scores, gids


def shard_devices(n_shards: int, devices=None) -> list:
    """Devices that hold the shards: one shard per device when there
    are enough of them, otherwise the largest device count that divides
    ``n_shards`` (consecutive shards then share a device)."""
    devices = list(jax.devices() if devices is None else devices)
    n_use = max(g for g in range(1, min(len(devices), n_shards) + 1)
                if n_shards % g == 0)
    return devices[:n_use]


def build_sharded_index(docs: PaddedSparse, cfg: SeismicConfig,
                        n_shards: int, *, list_chunk: int = 32,
                        devices=None, lists=None):
    """Build one local index per doc shard, each ON the device that will
    hold it (``shard_devices``: shard s on device s when there are at
    least ``n_shards``). Returns a stacked pytree whose every array leaf
    has a leading [n_shards] axis sharded over those devices, so no
    device ever holds another shard's index. ``docs`` may be host
    (numpy) arrays; only each shard's rows are sent to its device.
    ``lists`` restricts every shard's build as in ``build_index``."""
    sharded = shard_collection(docs, n_shards)
    devs = shard_devices(n_shards, devices)
    per_dev = n_shards // len(devs)

    def build(s):
        shard_docs = jax.device_put(
            PaddedSparse(sharded.coords[s], sharded.vals[s], docs.dim),
            devs[s // per_dev])
        return build_index(shard_docs, cfg, list_chunk=list_chunk,
                           lead=(1,), lists=lists)

    # one thread per shard: each device compiles its own programs, and
    # the compiles (and the host-driven list loops) overlap
    with ThreadPoolExecutor(n_shards) as pool:
        parts = list(pool.map(build, range(n_shards)))
    sharding = NamedSharding(Mesh(np.array(devs), ("shard",)), P("shard"))

    def assemble(*xs):
        groups = [xs[g] if per_dev == 1 else
                  jnp.concatenate(xs[g * per_dev:(g + 1) * per_dev])
                  for g in range(len(devs))]
        return jax.make_array_from_single_device_arrays(
            (n_shards,) + xs[0].shape[1:], sharding, groups)

    return jax.tree.map(assemble, *parts)


def shard_views(stacked) -> list:
    """Per-shard ``[1, ...]`` views of a stacked index, each on the
    device that holds that shard. With one shard per device the views
    are the device buffers themselves (no copy)."""
    leaves, treedef = jax.tree.flatten(stacked)
    n = leaves[0].shape[0]
    per_leaf = []
    for x in leaves:
        parts = [None] * n
        for sh in x.addressable_shards:
            lo = sh.index[0].start or 0
            for i in range(sh.data.shape[0]):
                if parts[lo + i] is None:
                    parts[lo + i] = sh.data if sh.data.shape[0] == 1 \
                        else sh.data[i:i + 1]
        per_leaf.append(parts)
    return [jax.tree.unflatten(treedef, [p[s] for p in per_leaf])
            for s in range(n)]


def _search_local(view, q_coords, q_vals, shard_offset, p: SearchParams,
                  n_docs: int | None):
    """One shard's search: the shared pipeline on the local index, ids
    globalized by ``shard_offset`` and pad hits masked to (-inf, -1)
    BEFORE anything crosses the shard boundary. ``view`` leaves are
    [1, ...]. Returns (scores, global ids, docs_evaluated)."""
    local = jax.tree.map(lambda x: x[0], view)
    scores, ids, ev = run_pipeline(local, q_coords, q_vals, p)
    scores, gids = mask_shard_topk(scores, ids, local.fwd, shard_offset,
                                   n_docs=n_docs)
    return scores, gids, ev


@partial(jax.jit, static_argnames=("p", "n_docs"))
def search_shard(view, q_coords, q_vals, shard_offset, p: SearchParams,
                 n_docs: int | None):
    """The per-shard launch of ``ReplicaSeismicServer(mode="shard")``
    (:func:`_search_local`, jitted); it runs on the device that holds
    ``view``."""
    return _search_local(view, q_coords, q_vals, shard_offset, p, n_docs)


def place_on_mesh(stacked, mesh, doc_axes=("model",)):
    """Lay a stacked index out for :func:`make_distributed_search` on
    ``mesh``: sharded over ``doc_axes``, replicated over the other axes.
    Where the build already put shard s on the mesh's s-th doc device,
    nothing moves."""
    return jax.device_put(stacked, NamedSharding(mesh, P(doc_axes)))


def make_distributed_search(mesh, p: SearchParams,
                            doc_axes=("model",), data_axis="data",
                            *, n_docs: int | None = None):
    """Returns ``search(stacked_index, q_coords, q_vals) -> (scores, ids)``
    running under shard_map on ``mesh``.

    stacked_index leaves: [n_doc_shards, ...] sharded over ``doc_axes``
    (``place_on_mesh`` lays a ``build_sharded_index`` result out so).
    q_coords/q_vals: [Q, nnz] sharded over ``data_axis``.
    output: (scores [Q,k], global ids [Q,k]) sharded over ``data_axis``.
    ``n_docs``: the LIVE corpus size (pre-padding ``docs.n``); when
    given, any globalized id at or past it is masked before the merge
    in addition to the content-based pad masking.
    """
    index_spec = P(doc_axes)
    q_spec = P(data_axis)

    def local_search(index_shard, q_coords, q_vals):
        # every leaf arrives as [1, ...] on its doc-shard device
        per_shard = index_shard.fwd.coords.shape[1]
        # globalize ids with the shard offset (row-major over doc axes)
        shard_id = jax.lax.axis_index(doc_axes[0])
        for ax in doc_axes[1:]:
            shard_id = shard_id * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        # the shared batch-first pipeline on the whole local query
        # batch (same code as local, served and replica-shard search);
        # pad-doc hits are masked before the all-gather
        scores, gids, _ = _search_local(index_shard, q_coords, q_vals,
                                        shard_id * per_shard, p, n_docs)

        # fan-in: gather every shard's top-k, merge
        all_s, all_g = scores, gids
        for ax in doc_axes:
            all_s = jax.lax.all_gather(all_s, ax)              # [Pax, Q, kk]
            all_g = jax.lax.all_gather(all_g, ax)
            all_s = jnp.moveaxis(all_s, 0, 1).reshape(scores.shape[0], -1)
            all_g = jnp.moveaxis(all_g, 0, 1).reshape(scores.shape[0], -1)
        top_s, pos = jax.lax.top_k(all_s, p.k)
        top_g = jnp.take_along_axis(all_g, pos, axis=-1)
        return top_s, top_g

    def search(stacked_index, q_coords, q_vals):
        specs = jax.tree.map(lambda _: index_spec, stacked_index)
        fn = jax.shard_map(
            local_search, mesh=mesh,
            in_specs=(specs, q_spec, q_spec),
            out_specs=(q_spec, q_spec),
            check_vma=False)  # outputs replicated over doc axes post-gather
        return fn(stacked_index, q_coords, q_vals)

    return search
