"""Plain reference of a dense decoder with grouped-query attention
(Llama-style: RMSNorm, rotary positions, SwiGLU MLP, untied head), and of
the Stretto operator answered over a compressed KV cache.

It imports nothing of the program. It follows the published architecture
and the operator's stated semantics:

  - prefill: the document's keys and values at every layer;
  - Expected-Attention compression (Devoto et al. 2025): a position's
    keep-score is its expected attention logit under a per-head Gaussian
    of future queries, fitted on the trailing half of the first eight
    documents, averaged over query heads and KV heads; a rung at ratio r
    keeps the max(4, round((1 - r) n)) best positions of each layer;
  - the int8 rung: per (position, head) absmax scales, round to nearest;
  - one query token decoded at the position after the kept cache;
  - a filter answers logit(yes) - logit(no); a map answers the value token
    with the largest logit, and the margin to the second.

Everything runs in float32 at `highest` matmul precision, on one document
at a time, from the weights the benchmark made (`make_params`, the
program's parameter layout). `mode="fp8"` is the control: the same
arithmetic with every matmul operand and the cached K/V rounded to
float8_e4m3fn under a per-tensor absmax scale.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0


def dims(c: Dict) -> Dict[str, int]:
    d = int(c["hidden_size"])
    H = int(c["num_attention_heads"])
    return {"L": int(c["num_hidden_layers"]), "d": d, "H": H,
            "KV": int(c["num_key_value_heads"]),
            "dh": int(c.get("head_dim") or d // H),
            "ff": int(c["intermediate_size"]), "V": int(c["vocab_size"]),
            "Vp": (int(c["vocab_size"]) + 255) // 256 * 256}


def param_shapes(c: Dict):
    """The program's parameter layout: layer weights stacked on a leading
    layer axis; (shape, init) per leaf."""
    m = dims(c)
    L, d, H, KV, dh, ff = m["L"], m["d"], m["H"], m["KV"], m["dh"], m["ff"]
    return {
        "embed": ((m["Vp"], d), "normal"),
        "head": ((d, m["Vp"]), "normal"),
        "final_norm": ((d,), "zeros"),
        "layers": {
            "attn": {"wq": ((L, d, H * dh), "normal"),
                     "wk": ((L, d, KV * dh), "normal"),
                     "wv": ((L, d, KV * dh), "normal"),
                     "wo": ((L, H * dh, d), "normal")},
            "mlp": {"w_gate": ((L, d, ff), "normal"),
                    "w_up": ((L, d, ff), "normal"),
                    "w_down": ((L, ff, d), "normal")},
            "norm_attn": ((L, d), "zeros"),
            "norm_mlp": ((L, d), "zeros"),
        },
    }


def make_params(c: Dict, key, dtype=jnp.bfloat16, std: float = 0.02):
    """Random weights in one jitted call on the default device: N(0, std)
    matrices and zero norm offsets (RMSNorm scales of 1)."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda v: isinstance(v, tuple) and len(v) == 2
        and isinstance(v[1], str))

    def build(k):
        keys = jax.random.split(k, len(leaves))
        out = []
        for (shape, init), kk in zip(leaves, keys):
            if init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append(jax.random.normal(kk, shape, dtype) * std)
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(key)


def _q8(x):
    """Round to float8_e4m3fn under a per-tensor absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _int8(x):
    """The int8 rung: per-(position, head) absmax over the head dim."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    return jnp.round(x / jnp.maximum(s, 1e-9)) * s


class Reference:
    """Jitted reference pieces for one configuration and one precision."""

    def __init__(self, c: Dict, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        self.c, self.m, self.mode = c, dims(c), mode
        self.theta = float(c["rope_theta"])
        self.eps = float(c["rms_norm_eps"])
        self.prefill = jax.jit(self._prefill)
        self.calib = jax.jit(self._calib)
        self.keep_mask = jax.jit(self._keep_mask)
        self.decode = jax.jit(self._decode, static_argnames="quant")

    # ---- primitives ----
    def _op(self, x):
        return _q8(x) if self.mode == "fp8" else x

    def _mm(self, a, b):
        return jnp.matmul(self._op(a.astype(F32)), self._op(b.astype(F32)),
                          precision=HIGHEST)

    def _norm(self, x, w):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * lax.rsqrt(var + self.eps) * (1.0 + w.astype(F32))

    def _rope(self, x, pos):
        """x (..., S, heads, dh); pos (..., S). Half-split rotation."""
        dh = x.shape[-1]
        freqs = 1.0 / (self.theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
        ang = pos[..., None].astype(F32) * freqs
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def _mlp(self, p, h):
        g = self._mm(h, p["w_gate"])
        return self._mm(g * jax.nn.sigmoid(g) * self._mm(h, p["w_up"]),
                        p["w_down"])

    # ---- prefill: keys/values (and normed layer inputs) of a batch ----
    def _forward(self, params, tokens):
        m = self.m
        B, S = tokens.shape
        x = params["embed"][tokens].astype(F32)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        causal = jnp.tril(jnp.ones((S, S), bool))
        G = m["H"] // m["KV"]

        def layer(x, p):
            a = p["attn"]
            h = self._norm(x, p["norm_attn"])
            q = self._rope(self._mm(h, a["wq"]).reshape(B, S, m["H"], m["dh"]),
                           pos)
            k = self._rope(self._mm(h, a["wk"]).reshape(B, S, m["KV"],
                                                        m["dh"]), pos)
            v = self._mm(h, a["wv"]).reshape(B, S, m["KV"], m["dh"])
            k, v = self._op(k), self._op(v)
            kh, vh = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", self._op(q), kh,
                           precision=HIGHEST) * m["dh"] ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", self._op(pr), vh,
                           precision=HIGHEST).reshape(B, S, -1)
            x = x + self._mm(o, a["wo"])
            x = x + self._mlp(p["mlp"], self._norm(x, p["norm_mlp"]))
            return x, (k, v, h)

        _, (k, v, h) = lax.scan(layer, x, params["layers"])
        return k, v, h                    # (L, B, S, KV, dh) x2, (L, B, S, d)

    def _prefill(self, params, tokens):
        k, v, _ = self._forward(params, tokens)
        return k, v

    def _calib(self, params, tokens, t0, t1):
        """Future-query Gaussians per (layer, KV head, group, dim) from the
        un-rotated queries of positions [t0, t1) of every row."""
        m = self.m
        _, _, h = self._forward(params, tokens)
        q = jnp.einsum("lbsd,lde->lbse", self._op(h),
                       self._op(params["layers"]["attn"]["wq"].astype(F32)),
                       precision=HIGHEST)
        S = tokens.shape[1]
        w = ((jnp.arange(S) >= t0) & (jnp.arange(S) < t1)).astype(F32)
        n = w.sum() * tokens.shape[0]
        G = m["H"] // m["KV"]
        q = q.reshape(m["L"], tokens.shape[0], S, m["KV"], G, m["dh"])
        wb = w[None, None, :, None, None, None]
        mu = (q * wb).sum(axis=(1, 2)) / n
        sig2 = (jnp.square(q - mu[:, None, None]) * wb).sum(axis=(1, 2)) / n
        return mu, sig2                   # (L, KV, G, dh)

    def _keep_mask(self, k, mu, sig2, n, keep):
        """Kept positions of one document (k: (L, S, KV, dh))."""
        scale = k.shape[-1] ** -0.5
        lin = jnp.einsum("lskd,lkgd->lskg", k, mu, precision=HIGHEST)
        quad = jnp.einsum("lskd,lkgd->lskg", k * k, sig2, precision=HIGHEST)
        score = (lin * scale + 0.5 * quad * scale * scale).mean(-1).mean(-1)
        pos = jnp.arange(k.shape[1])
        score = jnp.where(pos[None, :] < n, score, -jnp.inf)
        rank = jnp.argsort(jnp.argsort(-score, axis=-1, stable=True),
                           axis=-1, stable=True)
        return (rank < keep) & (pos[None, :] < n)

    def _decode(self, params, k, v, kept, qpos, tokens, answer, quant):
        """Answer logits of query tokens `tokens` (Q,), each decoded alone
        at position `qpos` against the kept cache. k, v: (L, S, KV, dh)."""
        m = self.m
        G = m["H"] // m["KV"]
        if quant:
            k, v = _int8(k), _int8(v)
        k, v = self._op(k), self._op(v)

        def one(tok):
            x = params["embed"][tok].astype(F32)[None]          # (1, d)
            pos = qpos[None]

            def layer(x, inp):
                p, kl, vl, keep_l = inp
                a = p["attn"]
                h = self._norm(x, p["norm_attn"])
                q = self._rope(self._mm(h, a["wq"]).reshape(1, m["H"],
                                                            m["dh"]), pos)[0]
                kn = self._rope(self._mm(h, a["wk"]).reshape(1, m["KV"],
                                                             m["dh"]), pos)[0]
                vn = self._mm(h, a["wv"]).reshape(m["KV"], m["dh"])
                if quant:
                    kn, vn = _int8(kn), _int8(vn)
                kn, vn = self._op(kn), self._op(vn)
                kc = jnp.concatenate([kl, kn[None]], 0)          # (S+1,KV,dh)
                vc = jnp.concatenate([vl, vn[None]], 0)
                valid = jnp.concatenate([keep_l, jnp.ones((1,), bool)])
                kh, vh = jnp.repeat(kc, G, axis=1), jnp.repeat(vc, G, axis=1)
                s = jnp.einsum("hd,shd->hs", self._op(q), kh,
                               precision=HIGHEST) * m["dh"] ** -0.5
                s = jnp.where(valid[None], s, -jnp.inf)
                pr = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("hs,shd->hd", self._op(pr), vh,
                               precision=HIGHEST).reshape(1, -1)
                x = x + self._mm(o, a["wo"])
                x = x + self._mlp(p["mlp"], self._norm(x, p["norm_mlp"]))
                return x, None

            x, _ = lax.scan(layer, x, (params["layers"], k, v, kept))
            x = self._norm(x, params["final_norm"])
            return self._mm(x, params["head"][:, answer])[0]

        return jax.vmap(one)(tokens)


def keep_count(ratio: float, n: int) -> int:
    """Positions a rung keeps of an n-token document."""
    if ratio <= 0.0:
        return n
    return max(4, int(round((1.0 - ratio) * n)))
