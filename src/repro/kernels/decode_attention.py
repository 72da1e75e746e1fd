"""Pallas TPU flash-decode kernels over (compressed) KV caches.

The hot loop of Stretto's KV-cache-enabled operators: query tokens per
item attend to a precomputed, possibly compressed, right-padded cache.

Two entry points share one kernel:

  decode_attention        one query token per item (classic flash-decode)
  decode_query_attention  Lq query tokens per item in ONE dispatch — the
                          fused operator-query path: the serving engine
                          feeds the whole fixed query token list at once
                          instead of scanning tokens one at a time

  q        (B, KV, G, dk) / (B, Lq, KV, G, dk)   grouped GQA layout
  k_cache  (B, S, KV, dk)
  v_cache  (B, S, KV, dv)    dv may differ from dk (absorbed MLA: dv = r)
  lengths  (B,) int32        valid prefix per item (compressed lengths)
  window   int or traced int32 scalar; GLOBAL = full attention

`lengths` and `window` are scalar-prefetch operands: they land in SMEM
before the grid runs, and the kernel reads one scalar per item. `window`
is data, not a static closure constant, because the model's per-layer
window is carried through the layer scan (gemma3's local:global pattern)
and the kernel must accept a traced value without retracing per layer.

Grid (B, S/block_s): the KV-length axis iterates innermost and
sequentially on TPU, so the online-softmax state (m, l, acc) lives in VMEM
scratch across iterations — the TPU-idiomatic analogue of FlashDecoding's
split-K scheme. Each step streams one (block_s, KV, dk) K tile and its V
tile HBM->VMEM with every KV head in it: the minor two block dimensions
are then the array's own (KV, dk), which is what the TPU's tiling rule
accepts for any head count and head width, with no relayout of the cache.
The kernel loops over the KV heads in VMEM; per head, the (Lq*G, dk) x
(dk, block_s) score matmul and the (Lq*G, block_s) x (block_s, dv)
accumulate run on the MXU. Queries arrive as (KV, Lq*G, dk) rows so that
no reshape happens inside the kernel.

Per-item `lengths` masking makes padded batches exact — this is what lets
the serving engine batch caches of different compressed lengths. int8
variants take per-(token, head) scales (B, S, KV) and dequantize
in-register after the VMEM load, so HBM streams 1 byte/element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
GLOBAL = 1 << 30


def _window_arg(window) -> jax.Array:
    """Normalize the window kwarg (python int or traced scalar) to the
    (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(window, jnp.int32).reshape(1)


def _query_kernel(len_ref, win_ref, q_ref, k_ref, v_ref, *rest,
                  block_s: int, n_s: int, n_q: int, G: int, scale: float,
                  quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    window = win_ref[0]
    KV, rows, _ = q_ref.shape
    # row r holds query token r // G, at absolute position
    # length - n_q + r // G; causal masking against the cache positions
    # keeps the fused pass equivalent to the sequential per-token scan
    # (token i never sees tokens > i)
    k_pos = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)
    q_pos = length - n_q + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // G
    mask = (k_pos <= q_pos) & ((q_pos - k_pos) < window)

    for h in range(KV):
        q = q_ref[h].astype(jnp.float32) * scale          # (rows, dk)
        k = k_ref[:, h, :].astype(jnp.float32)            # (bs, dk)
        v = v_ref[:, h, :].astype(jnp.float32)            # (bs, dv)
        if quant:
            k = k * ks_ref[:, h:h + 1].astype(jnp.float32)
            v = v * vs_ref[:, h:h + 1].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, NEG_INF)                   # (rows, bs)

        m_prev = m_ref[h]                                 # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (rows, dv)
        m_ref[h] = m_new

    @pl.when(s_idx == n_s - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def decode_query_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, lengths: jax.Array, *,
                           window=GLOBAL, block_s: int = 128,
                           interpret: bool = False,
                           k_scale: jax.Array = None,
                           v_scale: jax.Array = None) -> jax.Array:
    """Fused multi-token query flash-decode. Returns (B, Lq, KV, G, dv).

    q: (B, Lq, KV, G, dk). `lengths` counts ALL valid tokens *including*
    the Lq query tokens (the cache already holds their k/v): query i's
    absolute position is lengths - Lq + i, and masking is causal per
    query token — one kernel dispatch replaces Lq sequential decode
    dispatches. With k_scale/v_scale (B, S, KV), the cache is int8 and
    dequantized in-register."""
    B, Lq, KV, G, dk = q.shape
    _, S, _, dv = v_cache.shape
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} must be a multiple of block_s={block_s}")
    n_s = S // block_s
    rows = Lq * G
    quant = k_scale is not None
    # (B, Lq, KV, G, dk) -> (B, KV, Lq*G, dk): one 2-D row block per head
    q_rows = jnp.swapaxes(q, 1, 2).reshape(B, KV, rows, dk)

    in_specs = [
        pl.BlockSpec((None, KV, rows, dk), lambda b, s, *_: (b, 0, 0, 0)),
        pl.BlockSpec((None, block_s, KV, dk), lambda b, s, *_: (b, s, 0, 0)),
        pl.BlockSpec((None, block_s, KV, dv), lambda b, s, *_: (b, s, 0, 0)),
    ]
    args = [q_rows, k_cache, v_cache]
    if quant:
        in_specs += [pl.BlockSpec((None, block_s, KV),
                                  lambda b, s, *_: (b, s, 0))] * 2
        args += [k_scale, v_scale]
    kern = functools.partial(_query_kernel, block_s=block_s, n_s=n_s,
                             n_q=Lq, G=G, scale=dk ** -0.5, quant=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_s),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, KV, rows, dv),
                                   lambda b, s, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, dv), q.dtype),
        interpret=interpret,
        name="decode_query_attention",
    )(lengths.astype(jnp.int32), _window_arg(window), *args)
    return jnp.swapaxes(out.reshape(B, KV, Lq, G, dv), 1, 2)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, window=GLOBAL,
                     block_s: int = 128, interpret: bool = False,
                     k_scale: jax.Array = None, v_scale: jax.Array = None
                     ) -> jax.Array:
    """Flash-decode of one query token per item. Returns (B, KV, G, dv).

    The single-token case of `decode_query_attention` (Lq = 1: the query
    sits at position lengths - 1). With k_scale/v_scale (B, S, KV) given,
    k_cache/v_cache are int8 and are dequantized in-register (HBM streams
    1 B/elem)."""
    return decode_query_attention(
        q[:, None], k_cache, v_cache, lengths, window=window,
        block_s=block_s, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale)[:, 0]
