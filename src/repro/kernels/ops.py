"""Backend-selectable public wrappers for the Pallas kernels.

Every wrapper takes `backend`, one of:

  auto       compiled Pallas on TPU, jnp oracle elsewhere (default)
  pallas     force the Pallas kernel (interpret mode off-TPU, so the
             lowering is still exercised on CPU)
  interpret  force Pallas interpret mode (CI's lowering check)
  ref        force the pure-jnp oracle (bit-stable CPU baseline)

`backend=None` defers to the STRETTO_KERNELS environment variable, read
at call time (not import time) so tests and deployments can flip it
without reimporting. The serving engine resolves the backend once per
jitted flush function and passes it explicitly.

int8 KV caches are handled here too: Pallas backends dequantize
in-register inside the kernel, while the ref backend dequantizes up
front in float32 — same math, materialized differently.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pl
from repro.kernels.decode_attention import \
    decode_query_attention as _query_pl
from repro.kernels.expected_attention import \
    expected_attention_scores as _ea_pl
from repro.kernels.prefill_attention import prefill_attention as _prefill_pl

GLOBAL = 1 << 30
VALID_BACKENDS = ("auto", "pallas", "interpret", "ref")
ENV_VAR = "STRETTO_KERNELS"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend=None) -> str:
    """Normalize a backend choice: explicit arg wins, else STRETTO_KERNELS
    (read now, not at import), else 'auto'."""
    if backend is None or backend == "":
        backend = os.environ.get(ENV_VAR, "auto") or "auto"
    if backend not in VALID_BACKENDS:
        raise ValueError(
            f"unknown kernels backend {backend!r}; expected one of "
            f"{VALID_BACKENDS}")
    return backend


def runs_ref(backend) -> bool:
    """True when `backend` (already resolved) computes with the jnp
    oracle on this host: `ref`, or `auto` off the TPU."""
    return backend == "ref" or (backend == "auto" and not _on_tpu())


def _clamp_window(window):
    """A python-int window past GLOBAL means full attention: clamp it so
    it never overflows the int32 the mask is computed in."""
    return min(window, GLOBAL) if isinstance(window, int) else window


def _dequant(x, scale):
    import jax.numpy as jnp
    return x.astype(jnp.float32) * scale[..., None]


def decode_attention(q, k_cache, v_cache, lengths, *, window=GLOBAL,
                     backend=None, block_s: int = 128,
                     k_scale=None, v_scale=None):
    """Single-query flash-decode; (B, KV, G, dk) -> (B, KV, G, dv)."""
    backend = resolve_backend(backend)
    window = _clamp_window(window)
    if runs_ref(backend):
        if k_scale is not None:
            k_cache = _dequant(k_cache, k_scale)
            v_cache = _dequant(v_cache, v_scale)
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths,
                                        window=window)
    interpret = (backend == "interpret") or not _on_tpu()
    return _decode_pl(q, k_cache, v_cache, lengths, window=window,
                      block_s=block_s, interpret=interpret,
                      k_scale=k_scale, v_scale=v_scale)


def decode_query_attention(q, k_cache, v_cache, lengths, *, window=GLOBAL,
                           backend=None, block_s: int = 128,
                           k_scale=None, v_scale=None):
    """Fused multi-token query decode; (B, Lq, KV, G, dk) ->
    (B, Lq, KV, G, dv). `lengths` includes the Lq query tokens."""
    backend = resolve_backend(backend)
    window = _clamp_window(window)
    if runs_ref(backend):
        if k_scale is not None:
            k_cache = _dequant(k_cache, k_scale)
            v_cache = _dequant(v_cache, v_scale)
        return ref.decode_query_attention_ref(q, k_cache, v_cache, lengths,
                                              window=window)
    interpret = (backend == "interpret") or not _on_tpu()
    return _query_pl(q, k_cache, v_cache, lengths, window=window,
                     block_s=block_s, interpret=interpret,
                     k_scale=k_scale, v_scale=v_scale)


def prefill_attention(q, k, v, *, window=GLOBAL, causal: bool = True,
                      backend=None):
    backend = resolve_backend(backend)
    if runs_ref(backend):
        return ref.prefill_attention_ref(q, k, v, window=window,
                                         causal=causal)
    interpret = (backend == "interpret") or not _on_tpu()
    return _prefill_pl(q, k, v, window=window, causal=causal,
                       interpret=interpret)


def expected_attention_scores(k_cache, mu, sig2, *, backend=None):
    backend = resolve_backend(backend)
    if runs_ref(backend):
        return ref.expected_attention_scores_ref(k_cache, mu, sig2)
    interpret = (backend == "interpret") or not _on_tpu()
    return _ea_pl(k_cache, mu, sig2, interpret=interpret)
