"""Pallas TPU kernel for Expected-Attention compression scores.

Query-agnostic KV-cache compression (Devoto et al. 2025, used by Stretto §5):
score each cached position by its expected attention weight under the
model's *future-query distribution* q ~ N(mu_h, diag(sig2_h)):

    E_q[exp(q . k / sqrt(d))] = exp(mu_h . k / sqrt(d)
                                    + 0.5 * (k*k) . sig2_h / d)

aggregated (mean) over the query heads h attached to the KV head. Offline,
the top (1 - ratio) fraction of positions per item is kept.

Grid (B, S/block_s). Each step takes a (block_s, KV, dk) K tile with every
KV head in it, so the minor two block dimensions are the array's own
(KV, dk) — the layout the TPU's tiling rule accepts at any head count.
Per head the kernel runs two MXU matmuls, K (bs, dk) x mu^T (dk, G) and
K^2 (bs, dk) x sig2^T (dk, G), a log-domain add and a mean over G, and
writes that head's column of the (block_s, KV) score tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ea_kernel(k_ref, mu_ref, sig2_ref, o_ref, *, scale: float):
    for h in range(k_ref.shape[1]):
        k = k_ref[:, h, :].astype(jnp.float32)             # (bs, dk)
        mu = mu_ref[h].astype(jnp.float32)                 # (G, dk)
        sig2 = sig2_ref[h].astype(jnp.float32)             # (G, dk)
        lin = jax.lax.dot_general(k, mu, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        quad = jax.lax.dot_general(k * k, sig2, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        log_score = lin * scale + 0.5 * quad * (scale * scale)  # (bs, G)
        o_ref[:, h:h + 1] = jnp.mean(log_score, axis=1, keepdims=True)


def expected_attention_scores(k_cache: jax.Array, mu: jax.Array,
                              sig2: jax.Array, *, block_s: int = 256,
                              interpret: bool = False) -> jax.Array:
    """k_cache: (B, S, KV, dk); mu, sig2: (KV, G, dk) query-head stats.

    Returns log-scores (B, S, KV) — higher means more worth keeping.
    """
    B, S, KV, dk = k_cache.shape
    G = mu.shape[1]
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} not a multiple of block_s={block_s}")

    kernel = functools.partial(_ea_kernel, scale=dk ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(B, S // block_s),
        in_specs=[
            pl.BlockSpec((None, block_s, KV, dk), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((KV, G, dk), lambda b, s: (0, 0, 0)),
            pl.BlockSpec((KV, G, dk), lambda b, s: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_s, KV), lambda b, s: (b, s, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, KV), jnp.float32),
        interpret=interpret,
        name="expected_attention_scores",
    )(k_cache, mu, sig2)
