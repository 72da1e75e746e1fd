"""Query-agnostic KV-cache compression (Expected Attention; paper §5).

Offline pipeline:
  1. calibrate_query_stats — run the model over calibration items, capture
     per-layer hidden states, re-project them to queries, and fit per-head
     Gaussians N(mu, diag(sig2)) of the *future query* distribution.
  2. score positions with kernels.ops.expected_attention_scores.
  3. keep the top (1 - ratio) positions per item per layer (query-agnostic:
     the same compressed cache serves every semantic operator — the paper's
     reusability requirement).

Applicability: gqa/hymba compress k/v; mla compresses *latent rows*
([c_kv ; k_rope] scored against absorbed-query stats); rwkv6 has no
positional cache — inapplicable (see DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as KOPS
from repro.models import forward
from repro.models import layers as L


class QueryStats(NamedTuple):
    mu: jax.Array     # (L, KV, G, dk)
    sig2: jax.Array   # (L, KV, G, dk)


def calibrate_query_stats(params, cfg: ModelConfig, tokens=None,
                          embeds=None, tail_frac: float = 0.5) -> QueryStats:
    """Fit per-layer, per-head query Gaussians from calibration data.

    Future operator queries arrive *after* the document, so we fit on the
    trailing `tail_frac` positions' projected queries.
    """
    _, caches = forward(params, cfg, tokens=tokens, embeds=embeds,
                        collect_cache=True, collect_hidden=True)
    h = caches["h"]                                # (L, B, S, d)
    Ln, B, S, d = h.shape
    t0 = int(S * (1.0 - tail_frac))
    h = h[:, :, t0:, :]

    if cfg.attn_kind in ("gqa", "hymba"):
        wq = (params["layers"]["attn"]["attn"]["wq"]
              if cfg.attn_kind == "hymba"
              else params["layers"]["attn"]["wq"])     # (L, d, H*dh)
        q = jnp.einsum("lbsd,lde->lbse", h, wq)
        KV, G, dk = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.d_head
        q = q.reshape(Ln, -1, KV, G, dk)
        mu = q.mean(axis=1)
        sig2 = q.var(axis=1)
        return QueryStats(mu, sig2)
    if cfg.attn_kind == "mla":
        m = cfg.mla
        ap = params["layers"]["attn"]
        if m.q_lora_rank:
            q = jnp.einsum("lbsd,lde,lef->lbsf", h, ap["wq_a"], ap["wq_b"])
        else:
            q = jnp.einsum("lbsd,lde->lbse", h, ap["wq"])
        H = cfg.n_heads
        q = q.reshape(Ln, -1, H, m.qk_nope_dim + m.qk_rope_dim)
        q_nope = q[..., :m.qk_nope_dim]
        q_rope = q[..., m.qk_nope_dim:]
        # absorbed query: q_lat = q_nope @ W_uk  (r-dim, per head)
        w_kv_b = ap["w_kv_b"].reshape(Ln, m.kv_lora_rank, H,
                                      m.qk_nope_dim + m.v_head_dim)
        w_uk = w_kv_b[..., :m.qk_nope_dim]              # (L, r, H, nope)
        q_lat = jnp.einsum("lthn,lrhn->ltrh", q_nope, w_uk)
        q_lat = jnp.moveaxis(q_lat, -1, -2)             # (L, T, H, r)
        q_full = jnp.concatenate([q_lat, q_rope], axis=-1)
        mu = q_full.mean(axis=1)[:, None]               # (L, 1, H, r+rope)
        sig2 = q_full.var(axis=1)[:, None]
        return QueryStats(mu, sig2)
    raise ValueError(f"no positional cache to compress for {cfg.attn_kind}")


def _cache_keys(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.attn_kind in ("gqa", "hymba"):
        return ("k", "v")
    if cfg.attn_kind == "mla":
        return ("c_kv", "k_rope")
    return ()


def _ea_layers(k, mu, sig2, backend: str):
    """Expected-Attention log-scores for every layer of one item:
    k (L, 1, S, KV, d), mu/sig2 (L, KV, G, d) -> (L, 1, S, KV)."""
    return jax.vmap(lambda kl, mul, sl: KOPS.expected_attention_scores(
        kl, mul, sl, backend=backend))(k, mu, sig2)


# An eager Pallas call is traced and compiled anew every time, which in
# the offline phase means once per item and rung; jitted, the kernel
# compiles once per shape. The jnp oracle stays eager: jitting it could
# fuse and reorder its float math, and with it which positions are kept.
_ea_layers_jit = jax.jit(_ea_layers, static_argnames="backend")


def _layer_scores(k, mu, sig2):
    backend = KOPS.resolve_backend(None)
    if KOPS.runs_ref(backend):
        return _ea_layers(k, mu, sig2, "ref")
    return _ea_layers_jit(k, mu, sig2, backend=backend)


def score_positions(cfg: ModelConfig, cache: Dict[str, Any],
                    stats: QueryStats, length: int) -> jax.Array:
    """Per-layer keep-scores for one item. cache leaves: (L, 1, S, ...).
    Returns (L, S) — -inf beyond `length`."""
    if cfg.attn_kind in ("gqa", "hymba"):
        k = cache["k"]                                  # (L, 1, S, KV, dk)
        Ln, _, S, KV, dk = k.shape
        scores = _layer_scores(k, stats.mu, stats.sig2)  # (L, 1, S, KV)
        scores = scores[:, 0].mean(-1)                  # (L, S)
    else:  # mla: score latent rows [c_kv ; k_rope]
        lat = jnp.concatenate([cache["c_kv"], cache["k_rope"]], axis=-1)
        Ln, _, S, r = lat.shape
        lat4 = lat.reshape(Ln, 1, S, 1, r)              # (L, 1, S, KV=1, r)
        scores = _layer_scores(lat4, stats.mu, stats.sig2)
        scores = scores[:, 0, :, 0]                     # (L, S)
    pos = jnp.arange(scores.shape[-1])[None, :]
    return jnp.where(pos < length, scores, -jnp.inf)


def compress_item_cache(cfg: ModelConfig, cache: Dict[str, Any],
                        stats: QueryStats, ratio: float, length: int
                        ) -> Tuple[Dict[str, np.ndarray], int]:
    """Compress one item's cache (batch dim 1) to keep (1-ratio) tokens.

    Returns (numpy cache dict with seq length S', new_length). Kept
    positions stay in original order (per layer, positions may differ)."""
    if ratio <= 0.0 or not _cache_keys(cfg):
        out = {k: np.asarray(v[:, 0]) for k, v in cache.items()
               if k in _cache_keys(cfg)}
        out = {k: v[:, :length] for k, v in out.items()}
        _add_states(cfg, cache, out)
        return out, length
    keep = max(4, int(round((1.0 - ratio) * length)))
    scores = score_positions(cfg, cache, stats, length)   # (L, S)
    _, idx = jax.lax.top_k(scores, keep)                  # (L, keep)
    idx = jnp.sort(idx, axis=-1)
    out = {}
    for key in _cache_keys(cfg):
        arr = cache[key][:, 0]                            # (L, S, ...)
        out[key] = np.asarray(jnp.take_along_axis(
            arr, idx.reshape(idx.shape + (1,) * (arr.ndim - 2)), axis=1))
    _add_states(cfg, cache, out)
    return out, keep


def quantize_kv(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """int8 rung of the compression ladder (gqa/hymba caches only).

    Takes a compressed numpy cache dict with k/v of shape (L, S', KV, dh)
    and returns int8 k/v plus per-(layer, token, head) absmax scales
    (L, S', KV) float32 — the exact layout `init_cache(..., quant=True)`
    uses and `_decode_kernel_int8` consumes. Dequantization is
    x_int8 * scale, matching decode_step's on-the-fly quantization of
    fresh query tokens, so stored context and new tokens share one
    numeric scheme. Non-k/v entries (hymba conv/ssm states) pass through
    untouched.
    """
    out = dict(arrays)
    for key in ("k", "v"):
        if key not in arrays:
            continue
        x = np.asarray(arrays[key], np.float32)           # (L, S', KV, dh)
        scale = np.max(np.abs(x), axis=-1) / 127.0        # (L, S', KV)
        q = np.round(x / np.maximum(scale, 1e-9)[..., None]).astype(np.int8)
        out[key] = q
        out[f"{key}_scale"] = scale.astype(np.float32)
    return out


def _add_states(cfg: ModelConfig, cache, out):
    """Hymba carries O(1) SSM/conv states alongside the compressible
    attention cache; they are copied through untouched."""
    for key in ("conv", "ssm"):
        if key in cache:
            out[key] = np.asarray(cache[key][:, 0])


def prune_dominated(profiles):
    """Drop profiles strictly worse in quality with no cost/storage gain
    (paper §5 offline phase). profiles: list of dicts with keys
    'ratio', 'quality', 'cost'."""
    kept = []
    for p in profiles:
        dominated = any(
            (q["quality"] >= p["quality"] and q["cost"] <= p["cost"]
             and (q["quality"] > p["quality"] or q["cost"] < p["cost"]))
            for q in profiles if q is not p)
        if not dominated:
            kept.append(p)
    return kept
