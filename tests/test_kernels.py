"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.expected_attention import expected_attention_scores
from repro.kernels.prefill_attention import prefill_attention

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


DECODE_CASES = [
    # (B, KV, G, dk, dv, S, block_s, window, dtype)
    (2, 2, 4, 64, 64, 256, 128, 1 << 30, jnp.float32),
    (3, 1, 8, 128, 128, 384, 128, 1 << 30, jnp.float32),
    (1, 4, 1, 64, 64, 128, 64, 1 << 30, jnp.bfloat16),
    (2, 2, 2, 64, 32, 256, 128, 1 << 30, jnp.float32),   # dv != dk (MLA)
    (2, 2, 4, 64, 64, 256, 128, 64, jnp.float32),        # windowed
    (1, 1, 4, 256, 128, 512, 128, 1 << 30, jnp.float32),  # latent-wide
    (3, 2, 7, 128, 128, 384, 128, 1 << 30, jnp.float32),  # G = 7 (Yi-34B)
    (4, 1, 7, 128, 128, 256, 128, 1 << 30, jnp.bfloat16),  # G = 7, bf16
]


@pytest.mark.parametrize("B,KV,G,dk,dv,S,bs,window,dtype", DECODE_CASES)
def test_decode_attention_sweep(B, KV, G, dk, dv, S, bs, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, KV, G, dk), dtype)
    k = _rand(ks[1], (B, S, KV, dk), dtype)
    v = _rand(ks[2], (B, S, KV, dv), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, S + 1, B), jnp.int32)
    out = decode_attention(q, k, v, lengths, window=window, block_s=bs,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


PREFILL_CASES = [
    # (B, S, KV, G, dk, dv, bq, bk, window, causal, dtype)
    (2, 256, 2, 2, 32, 32, 64, 64, 1 << 30, True, jnp.float32),
    (1, 512, 1, 4, 64, 64, 128, 128, 1 << 30, True, jnp.float32),
    (2, 256, 2, 2, 32, 32, 64, 64, 64, True, jnp.float32),
    (1, 256, 2, 1, 64, 64, 128, 64, 1 << 30, False, jnp.float32),
    (1, 256, 1, 2, 32, 32, 64, 64, 1 << 30, True, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,KV,G,dk,dv,bq,bk,window,causal,dtype",
                         PREFILL_CASES)
def test_prefill_attention_sweep(B, S, KV, G, dk, dv, bq, bk, window,
                                 causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, S, KV, G, dk), dtype)
    k = _rand(ks[1], (B, S, KV, dk), dtype)
    v = _rand(ks[2], (B, S, KV, dv), dtype)
    out = prefill_attention(q, k, v, window=window, causal=causal,
                            block_q=bq, block_k=bk, interpret=True)
    want = ref.prefill_attention_ref(q, k, v, window=window, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


EA_CASES = [
    (2, 256, 3, 64, 4, 128, jnp.float32),
    (1, 512, 1, 128, 8, 256, jnp.float32),
    (2, 128, 2, 32, 1, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,KV,dk,G,bs,dtype", EA_CASES)
def test_expected_attention_sweep(B, S, KV, dk, G, bs, dtype):
    ks = jax.random.split(KEY, 3)
    kc = _rand(ks[0], (B, S, KV, dk), dtype)
    mu = _rand(ks[1], (KV, G, dk), jnp.float32)
    sig2 = jnp.abs(_rand(ks[2], (KV, G, dk), jnp.float32))
    out = expected_attention_scores(kc, mu, sig2, block_s=bs, interpret=True)
    want = ref.expected_attention_scores_ref(kc, mu, sig2)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol,
                               rtol=tol)


def test_decode_masking_exact():
    """Entries beyond `lengths` must not influence the output at all."""
    B, KV, G, dk, S = 1, 1, 2, 32, 128
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, KV, G, dk), jnp.float32)
    k = _rand(ks[1], (B, S, KV, dk), jnp.float32)
    v = _rand(ks[2], (B, S, KV, dk), jnp.float32)
    lengths = jnp.asarray([40], jnp.int32)
    out1 = decode_attention(q, k, v, lengths, block_s=64, interpret=True)
    k2 = k.at[:, 40:].set(1e4)     # poison the padding
    v2 = v.at[:, 40:].set(-1e4)
    out2 = decode_attention(q, k2, v2, lengths, block_s=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_decode_attention_int8():
    """int8 KV + in-register dequant must match the dequantized oracle."""
    B, KV, G, dk, S = 2, 2, 4, 64, 256
    ks = jax.random.split(KEY, 3)
    k_f = jax.random.normal(ks[0], (B, S, KV, dk), jnp.float32)
    v_f = jax.random.normal(ks[1], (B, S, KV, dk), jnp.float32)
    q = jax.random.normal(ks[2], (B, KV, G, dk), jnp.float32)
    k_s = jnp.max(jnp.abs(k_f), -1) / 127.0
    v_s = jnp.max(jnp.abs(v_f), -1) / 127.0
    k_q = jnp.round(k_f / k_s[..., None]).astype(jnp.int8)
    v_q = jnp.round(v_f / v_s[..., None]).astype(jnp.int8)
    lengths = jnp.asarray([256, 100], jnp.int32)
    out = decode_attention(q, k_q, v_q, lengths, block_s=128,
                           interpret=True, k_scale=k_s, v_scale=v_s)
    want = ref.decode_attention_ref(q, k_q.astype(jnp.float32) *
                                    k_s[..., None],
                                    v_q.astype(jnp.float32) * v_s[..., None],
                                    lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
