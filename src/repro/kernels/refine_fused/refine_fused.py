"""Pallas kernel: one fused refine round (Seismic + kNN-graph stage 6).

The staged refine round materializes three HBM intermediates per
round: the [Q, k*degree] neighbor expansion, its sorted/deduped copy,
and the [Q, C, nnz] gathered forward rows for rescoring. This kernel
runs neighbor expand -> sort-based dedupe -> seen-mask -> candidate
compaction -> forward gather -> exact dot in ONE launch; only the
round's results (cand [Q, C], scores [Q, C]) leave VMEM.

Math is op-for-op identical to the unfused round (graph.refine +
scorer.dedupe_batch + scorer.score_candidates), with compaction
(fuse_level >= 1 packs live candidates to a prefix) applied in-kernel,
so the merged top-k is bit-exact across fuse levels — parity tests pin
it.

Coverage boundary (see src/repro/kernels/README.md): graph and forward
planes ride in whole-array blocks — exact under interpret mode (CPU
CI); Mosaic needs them VMEM-resident or an ANY-space DMA variant, plus
in-kernel sort support. Real-TPU validation is the ROADMAP follow-on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -jnp.inf


def _refine_round_kernel(ids_ref, scored_ref, q_ref, knn_ref, fwdc_ref,
                         fwdv_ref, *rest, n_docs, degree, quant):
    if quant:
        fs_ref, fz_ref, cand_ref, out_ref = rest
    else:
        cand_ref, out_ref = rest
    ids = ids_ref[...]                          # [tq, k]
    scored = scored_ref[...]                    # [tq, W]
    q = q_ref[...]                              # [tq, d]
    tq, k = ids.shape
    # ---- expand: graph neighbors of the current top-k
    safe = jnp.clip(ids, 0, n_docs - 1)
    nbrs = jnp.take(knn_ref[...], safe, axis=0,
                    mode="clip")[..., :degree]  # [tq, k, deg]
    nbrs = jnp.where(ids[..., None] >= 0, nbrs, n_docs)
    cand = nbrs.reshape(tq, k * degree).astype(jnp.int32)
    # ---- dedupe within the expansion (sort + neighbor mask)
    s = jnp.sort(cand, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((tq, 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
    cand = jnp.where(dup, n_docs, s)
    # ---- mask ids scored in any earlier round / the original merge
    seen = (cand[:, :, None] == scored[:, None, :]).any(-1)
    cand = jnp.where(seen, n_docs, cand)
    # ---- compaction: pack the live frontier to a sorted prefix
    cand = jnp.sort(cand, axis=-1)
    # ---- exact rescore through the scorer's forward plane
    c = jnp.take(fwdc_ref[...], cand, axis=0,
                 mode="clip").astype(jnp.int32)             # [tq, C, nnz]
    v = jnp.take(fwdv_ref[...], cand, axis=0, mode="clip")
    nnz = c.shape[-1]
    gathered = jnp.take_along_axis(
        q, c.reshape(tq, -1), axis=1).reshape(tq, k * degree, nnz)
    if quant:
        u8 = v.astype(q.dtype)
        sc = jnp.take(fs_ref[...], cand, mode="clip").astype(q.dtype)
        zc = jnp.take(fz_ref[...], cand, mode="clip").astype(q.dtype)
        deq = (u8 - 1.0) * sc[..., None] + zc[..., None]
        v = jnp.where(u8 > 0, deq, 0.0)         # level 0 == padding
    else:
        v = v.astype(q.dtype)
    scores = (gathered * v).sum(axis=-1)
    cand_ref[...] = cand
    out_ref[...] = jnp.where(cand < n_docs, scores, NEG)


@functools.partial(jax.jit, static_argnames=("n_docs", "degree", "tile_q",
                                             "interpret"))
def refine_round_pallas(ids: jax.Array, scored: jax.Array,
                        q_dense: jax.Array, knn_ids: jax.Array,
                        fwd_coords: jax.Array, fwd_vals: jax.Array,
                        fwd_scale: jax.Array | None = None,
                        fwd_zero: jax.Array | None = None, *,
                        n_docs: int, degree: int, tile_q: int = 8,
                        interpret: bool = True
                        ) -> tuple[jax.Array, jax.Array]:
    """One fused refine round.

    ids [Q, k] (-1 padding), scored [Q, W] (sentinel-padded already-
    scored ids) -> (cand [Q, k*degree] packed live-prefix frontier,
    scores [Q, k*degree] with sentinels at -inf). Q % tile_q == 0
    (ops.py pads).
    """
    qn, k = ids.shape
    w = scored.shape[1]
    d = q_dense.shape[1]
    n, nnz = fwd_coords.shape
    assert q_dense.shape[0] == qn and qn % tile_q == 0, (
        q_dense.shape, ids.shape, tile_q)
    assert 0 < degree <= knn_ids.shape[1], (degree, knn_ids.shape)
    grid = (qn // tile_q,)
    c_out = k * degree
    quant = fwd_scale is not None
    plane2 = lambda a, b: pl.BlockSpec((a, b), lambda i: (0, 0))  # noqa: E731
    in_specs = [
        pl.BlockSpec((tile_q, k), lambda i: (i, 0)),
        pl.BlockSpec((tile_q, w), lambda i: (i, 0)),
        pl.BlockSpec((tile_q, d), lambda i: (i, 0)),
        plane2(n, knn_ids.shape[1]),
        plane2(n, nnz), plane2(n, nnz),
    ]
    args = [ids, scored, q_dense, knn_ids, fwd_coords, fwd_vals]
    if quant:
        in_specs += [pl.BlockSpec((n,), lambda i: (0,))] * 2
        args += [fwd_scale, fwd_zero]
    out_spec = pl.BlockSpec((tile_q, c_out), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_refine_round_kernel, n_docs=n_docs,
                          degree=degree, quant=quant),
        grid=grid,
        in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((qn, c_out), jnp.int32),
            jax.ShapeDtypeStruct((qn, c_out), q_dense.dtype),
        ),
        name="refine_round",
        interpret=interpret,
    )(*args)
