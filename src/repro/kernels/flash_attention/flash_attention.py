"""Pallas TPU kernel: block-tiled softmax attention (FlashAttention-style).

Beyond-paper performance layer for the assigned LM architectures: the
prefill/train attention hot-spot, tiled for VMEM with the online-softmax
recurrence so the [S, S] score matrix never materializes in HBM.

Grid = (batch*heads, n_q_tiles, n_k_tiles), k innermost; running
(m, l, acc) state lives in VMEM scratch across the k sweep. MXU-aligned
tiles (128 defaults). Supports causal masking and sliding-window
(Gemma-style local) attention; kv-length masking covers padded keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               sm_scale, causal, window, kv_len, tile_q, tile_k, n_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [tq, d]
    k = k_ref[0].astype(jnp.float32)            # [tk, d]
    v = v_ref[0].astype(jnp.float32)            # [tk, d]
    s = (q @ k.T) * sm_scale                    # [tq, tk]

    q_pos = qi * tile_q + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
    k_pos = kj * tile_k + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1)
    mask = k_pos < kv_len                       # padded keys
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows -> 0
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "causal", "window", "kv_len", "tile_q", "tile_k",
    "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           sm_scale: float, causal: bool = True,
                           window: int | None = None, kv_len: int,
                           tile_q: int = 128, tile_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q [BH, Sq, D], k/v [BH, Sk, D] -> out [BH, Sq, D].

    Sq % tile_q == 0 and Sk % tile_k == 0 (ops.py pads); ``kv_len``
    masks padded key positions.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % tile_q == 0 and sk % tile_k == 0
    n_q, n_k = sq // tile_q, sk // tile_k
    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal, window=window,
        kv_len=kv_len, tile_q=tile_q, tile_k=tile_k, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q,), jnp.float32),       # running max
            pltpu.VMEM((tile_q,), jnp.float32),       # running denom
            pltpu.VMEM((tile_q, d), jnp.float32),     # accumulator
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
