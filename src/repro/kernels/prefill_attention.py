"""Pallas TPU causal/windowed flash attention (prefill & training target).

Used offline to build KV caches (paper §5: the one-time prefill over the
corpus) and as the TPU replacement for the jnp blocked-attention oracle in
train/prefill steps.

Grid (B, KV, nq, nk): nk iterates innermost/sequentially; online-softmax
state lives in VMEM scratch per q-block. Fully-masked (kj, qi) pairs —
above the causal diagonal or outside the sliding window — are skipped with
@pl.when, so compute for causal attention is ~half the rectangle and for
windowed attention proportional to the band (the paper's gemma3-style local
layers). Shapes: block_q x block_k multiples of 128 for the MXU; G query
heads per KV head ride the sublane dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
GLOBAL = 1 << 30


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    block_q: int, block_k: int, n_k: int, window: int,
                    scale: float, causal: bool):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kj * block_k
    # live iff some (q, k) pair in the tile satisfies the mask
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    live = jnp.logical_and(live, q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0].astype(jnp.float32) * scale    # (bq, G, dk)
        k = k_ref[0, :, 0].astype(jnp.float32)            # (bk, dk)
        v = v_ref[0, :, 0].astype(jnp.float32)            # (bk, dv)
        bq, G, dk = q.shape
        q2 = q.reshape(bq * G, dk)
        s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s.reshape(bq, G, -1)                          # (bq, G, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, s.shape[-1]), 2)
        mask = (q_pos - k_pos) < window
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.reshape(bq * G, -1), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(bq, G, -1)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = out.astype(o_ref.dtype)


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      window: int = GLOBAL, causal: bool = True,
                      block_q: int = 256, block_k: int = 256,
                      interpret: bool = False) -> jax.Array:
    """q: (B, S, KV, G, dk); k: (B, S, KV, dk); v: (B, S, KV, dv).
    Returns (B, S, KV, G, dv)."""
    B, S, KV, G, dk = q.shape
    dv = v.shape[-1]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not a multiple of blocks")
    n_q, n_k = S // block_q, S // block_k
    scale = dk ** -0.5

    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        window=window, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, G, dk),
                         lambda b, h, i, j: (b, i, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, dk),
                         lambda b, h, i, j: (b, j, h, 0)),
            pl.BlockSpec((1, block_k, 1, dv),
                         lambda b, h, i, j: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, G, dv),
                               lambda b, h, i, j: (b, i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, KV, G, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, G, 1), jnp.float32),
            pltpu.VMEM((block_q, G, 1), jnp.float32),
            pltpu.VMEM((block_q, G, dv), jnp.float32),
        ],
        interpret=interpret,
        name="prefill_attention",
    )(q, k, v)
