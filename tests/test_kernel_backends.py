"""Backend-selectable attention ops: dispatch rules, interpret-vs-ref
parity (the CPU oracle contract), the fused multi-token query kernel, and
int8 KV dequantization — deterministic sweeps plus hypothesis property
twins (the property tests skip when the optional dep is absent)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from hypothesis_compat import given, settings, st  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402

KEY = jax.random.PRNGKey(7)
GLOBAL = 1 << 30


def _inputs(seed, B, S, KV, G, dk, dv, lq=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qshape = (B, KV, G, dk) if lq is None else (B, lq, KV, G, dk)
    q = jax.random.normal(ks[0], qshape, jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, dk), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, dv), jnp.float32).astype(dtype)
    lengths = jnp.asarray(
        np.random.default_rng(seed).integers(
            1 if lq is None else (lq or 1), S + 1, B), jnp.int32)
    return q, k, v, lengths


def _quantize(x):
    scale = jnp.max(jnp.abs(x), -1) / 127.0
    q = jnp.round(x / jnp.maximum(scale, 1e-9)[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------

def test_resolve_backend_precedence(monkeypatch):
    monkeypatch.delenv(ops.ENV_VAR, raising=False)
    assert ops.resolve_backend(None) == "auto"
    assert ops.resolve_backend("") == "auto"
    # env provides the default ...
    monkeypatch.setenv(ops.ENV_VAR, "ref")
    assert ops.resolve_backend(None) == "ref"
    # ... but an explicit argument wins
    assert ops.resolve_backend("interpret") == "interpret"
    # env is read at call time, not import time
    monkeypatch.setenv(ops.ENV_VAR, "interpret")
    assert ops.resolve_backend(None) == "interpret"


def test_resolve_backend_rejects_unknown(monkeypatch):
    with pytest.raises(ValueError, match="backend"):
        ops.resolve_backend("cuda")
    monkeypatch.setenv(ops.ENV_VAR, "nonsense")
    with pytest.raises(ValueError, match="backend"):
        ops.resolve_backend(None)


def test_env_backend_reaches_the_op(monkeypatch):
    """STRETTO_KERNELS routes the actual computation: ref and interpret
    agree numerically but go through different code paths (interpret
    raises on an illegal grid, ref does not)."""
    q, k, v, lengths = _inputs(0, 2, 128, 2, 2, 32, 32)
    monkeypatch.setenv(ops.ENV_VAR, "ref")
    out_ref = ops.decode_attention(q, k, v, lengths)
    monkeypatch.setenv(ops.ENV_VAR, "interpret")
    out_int = ops.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_int),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# interpret-vs-ref parity sweeps (deterministic)
# ---------------------------------------------------------------------------

PARITY_CASES = [
    # (B, S, KV, G, dk, dv, window)   — GQA group counts, ragged lengths
    (2, 256, 2, 4, 64, 64, GLOBAL),
    (3, 128, 1, 8, 32, 32, GLOBAL),   # MQA-style single KV head
    (1, 384, 4, 1, 64, 64, GLOBAL),   # one query per KV head
    (2, 256, 2, 2, 64, 32, GLOBAL),   # dv != dk
    (2, 256, 2, 4, 64, 64, 64),       # sliding window
    (4, 128, 2, 2, 32, 32, 17),       # window not a block multiple
    (3, 256, 2, 7, 64, 64, GLOBAL),   # G = 7 (Yi-34B): rows not a power of 2
    (2, 384, 1, 7, 128, 128, 100),    # G = 7, d_head 128, sliding window
]


@pytest.mark.parametrize("B,S,KV,G,dk,dv,window", PARITY_CASES)
def test_decode_parity(B, S, KV, G, dk, dv, window):
    q, k, v, lengths = _inputs(B + S, B, S, KV, G, dk, dv)
    out = ops.decode_attention(q, k, v, lengths, window=window,
                               backend="interpret")
    want = ops.decode_attention(q, k, v, lengths, window=window,
                                backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("B,S,KV,G,dk,dv,window", PARITY_CASES)
def test_decode_parity_int8(B, S, KV, G, dk, dv, window):
    q, k, v, lengths = _inputs(B + S + 1, B, S, KV, G, dk, dv)
    k_q, k_s = _quantize(k)
    v_q, v_s = _quantize(v)
    out = ops.decode_attention(q, k_q, v_q, lengths, window=window,
                               backend="interpret", k_scale=k_s,
                               v_scale=v_s)
    want = ops.decode_attention(q, k_q, v_q, lengths, window=window,
                                backend="ref", k_scale=k_s, v_scale=v_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # and the quantization itself stays close to the f32 cache
    f32 = ops.decode_attention(q, k, v, lengths, window=window,
                               backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(f32), atol=5e-2)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 8),
       st.integers(0, 2 ** 31), st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_decode_parity_property(B, KV, G, window_exp, seed):
    """Property twin of the sweep: any (B, KV, G, window, lengths) combo
    must agree between interpret and ref."""
    window = max(1, window_exp)
    q, k, v, lengths = _inputs(seed % 10_000, B, 128, KV, G, 32, 32)
    out = ops.decode_attention(q, k, v, lengths, window=window,
                               backend="interpret", block_s=64)
    want = ops.decode_attention(q, k, v, lengths, window=window,
                                backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# fused multi-token query kernel
# ---------------------------------------------------------------------------

QUERY_CASES = [
    # (B, S, KV, G, dk, dv, Lq, window)
    (2, 256, 2, 4, 64, 64, 6, GLOBAL),
    (3, 128, 1, 8, 32, 32, 4, GLOBAL),
    (2, 256, 2, 2, 64, 32, 6, GLOBAL),  # dv != dk
    (2, 256, 2, 4, 64, 64, 6, 64),      # sliding window
    (1, 128, 2, 2, 32, 32, 1, GLOBAL),  # Lq=1 degenerate
    (3, 256, 2, 7, 64, 64, 6, GLOBAL),  # G = 7 (Yi-34B), ragged lengths
    (4, 384, 1, 7, 128, 128, 1, GLOBAL),  # G = 7, Lq=1: 7 query rows
]


@pytest.mark.parametrize("B,S,KV,G,dk,dv,Lq,window", QUERY_CASES)
def test_query_parity(B, S, KV, G, dk, dv, Lq, window):
    q, k, v, lengths = _inputs(B * S + Lq, B, S, KV, G, dk, dv, lq=Lq)
    out = ops.decode_query_attention(q, k, v, lengths, window=window,
                                     backend="interpret")
    want = ops.decode_query_attention(q, k, v, lengths, window=window,
                                      backend="ref")
    assert out.shape == (B, Lq, KV, G, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_query_lq1_matches_decode():
    """A fused call with one query token IS single-token decode."""
    q, k, v, lengths = _inputs(3, 2, 256, 2, 4, 64, 64)
    for backend in ("ref", "interpret"):
        multi = ops.decode_query_attention(q[:, None], k, v, lengths,
                                           backend=backend)
        single = ops.decode_attention(q, k, v, lengths, backend=backend)
        np.testing.assert_allclose(np.asarray(multi[:, 0]),
                                   np.asarray(single), atol=1e-5)


def test_query_masking_exact():
    """Positions beyond each item's length must contribute exactly
    nothing: poison the padding with huge values and compare against a
    clean cache."""
    B, S, KV, G, dk, Lq = 2, 256, 2, 2, 32, 4
    q, k, v, _ = _inputs(11, B, S, KV, G, dk, dk, lq=Lq)
    lengths = jnp.asarray([100, 37], jnp.int32)
    mask = (jnp.arange(S)[None, :, None, None]
            >= lengths[:, None, None, None])
    k_p = jnp.where(mask, 1e9, k)
    v_p = jnp.where(mask, 1e9, v)
    for backend in ("ref", "interpret"):
        clean = ops.decode_query_attention(q, k, v, lengths,
                                           backend=backend)
        poisoned = ops.decode_query_attention(q, k_p, v_p, lengths,
                                              backend=backend)
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))


def test_query_causal_within_window():
    """Inside the fused block, query token i must not see tokens i+1..:
    zeroing the still-future cache rows cannot change row i."""
    B, S, KV, G, dk, Lq = 1, 128, 1, 2, 32, 4
    q, k, v, _ = _inputs(13, B, S, KV, G, dk, dk, lq=Lq)
    lengths = jnp.asarray([64], jnp.int32)   # includes the Lq query rows
    first_pos = 64 - Lq                       # q_pos of query token 0
    k_cut = k.at[:, first_pos + 1:].set(0.0)
    v_cut = v.at[:, first_pos + 1:].set(0.0)
    for backend in ("ref", "interpret"):
        full = ops.decode_query_attention(q, k, v, lengths, backend=backend)
        cut = ops.decode_query_attention(q, k_cut, v_cut, lengths,
                                         backend=backend)
        np.testing.assert_allclose(np.asarray(full[:, 0]),
                                   np.asarray(cut[:, 0]), atol=1e-6)


@pytest.mark.parametrize("B,S,KV,G,dk,dv,Lq,window", QUERY_CASES[:2])
def test_query_parity_int8(B, S, KV, G, dk, dv, Lq, window):
    q, k, v, lengths = _inputs(B + Lq, B, S, KV, G, dk, dv, lq=Lq)
    k_q, k_s = _quantize(k)
    v_q, v_s = _quantize(v)
    out = ops.decode_query_attention(q, k_q, v_q, lengths, window=window,
                                     backend="interpret", k_scale=k_s,
                                     v_scale=v_s)
    want = ops.decode_query_attention(q, k_q, v_q, lengths, window=window,
                                      backend="ref", k_scale=k_s,
                                      v_scale=v_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@given(st.integers(1, 3), st.integers(1, 6), st.integers(0, 10 ** 6))
@settings(max_examples=15, deadline=None)
def test_query_parity_property(B, Lq, seed):
    q, k, v, lengths = _inputs(seed % 10_000, B, 128, 2, 2, 32, 32, lq=Lq)
    out = ops.decode_query_attention(q, k, v, lengths, backend="interpret",
                                     block_s=64)
    want = ops.decode_query_attention(q, k, v, lengths, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@given(st.integers(1, 3), st.integers(0, 10 ** 6))
@settings(max_examples=15, deadline=None)
def test_int8_scale_property(B, seed):
    """Property twin for int8: arbitrary positive per-token scales must
    dequantize identically on both backends."""
    q, k, v, lengths = _inputs(seed % 10_000, B, 128, 2, 2, 32, 32)
    k_q, k_s = _quantize(k)
    v_q, v_s = _quantize(v)
    out = ops.decode_attention(q, k_q, v_q, lengths, backend="interpret",
                               block_s=64, k_scale=k_s, v_scale=v_s)
    want = ops.decode_attention(q, k_q, v_q, lengths, backend="ref",
                                k_scale=k_s, v_scale=v_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_query_ref_oracle_softmax():
    """decode_query_attention_ref against a from-scratch softmax — the
    oracle itself must be right, not merely self-consistent."""
    B, S, KV, G, dk, Lq = 1, 32, 1, 2, 16, 3
    q, k, v, _ = _inputs(29, B, S, KV, G, dk, dk, lq=Lq)
    lengths = jnp.asarray([20], jnp.int32)
    out = ref.decode_query_attention_ref(q, k, v, lengths)
    qn, kn, vn = (np.asarray(x, np.float64) for x in (q, k, v))
    for li in range(Lq):
        q_pos = 20 - Lq + li
        for h in range(KV):
            for g in range(G):
                s = (kn[0, :, h] @ qn[0, li, h, g]) / np.sqrt(dk)
                s[q_pos + 1:] = -np.inf
                p = np.exp(s - s.max())
                p /= p.sum()
                want = p @ vn[0, :, h]
                np.testing.assert_allclose(np.asarray(out[0, li, h, g]),
                                           want, atol=1e-5)
