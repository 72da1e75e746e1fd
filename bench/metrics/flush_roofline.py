"""flush_roofline: the least time the chip needs for the layer loops of the
window's flushes over the device time of those loops, in percent.

A flush program runs the layers as one loop, a `%while` op in the trace,
which only flush programs have; the ops outside it (embedding gather,
final norm, head) carry names that the engine's small programs on the
logits share, so the trace cannot tell them apart and they are left out
of both sides. Least time per flush is the larger of the layers' FLOPs
over the bf16 peak and their bytes over HBM bandwidth: every layer's
matmuls and the attention over the real cache length for each scored
tuple; the layers' weights once per flush (one flush per device-LRU
lookup), plus the real K/V bytes and the query and output rows of each
scored tuple. Summing FLOPs and bytes over the window before taking the
larger can only lower the bound. Real cache lengths and bytes come from
the store's per-item metadata, so padding counts as waste, not work."""
import re

from sbench import flops as FL

WHILE_OP = re.compile(r"%while(\.\d+)? while")


def layer_weight_bytes(c) -> float:
    """Bytes of one pass over every layer's weights (bf16): the four
    attention projections, the three MLP matrices and the two norms."""
    m = FL.dims(c)
    d, H, KV, dh, ff = m["d"], m["H"], m["KV"], m["dh"], m["ff"]
    per_layer = 2 * d * H * dh + 2 * d * KV * dh + 3 * d * ff + 2 * d
    return 2.0 * m["L"] * per_layer


def layer_matmul_flops(c) -> float:
    """FLOPs of one token through every layer's matmuls (the head and the
    embedding left out), at 2 FLOP per multiply-add."""
    m = FL.dims(c)
    return FL.matmul_flops_per_token(c) - 2.0 * m["d"] * m["V"]


def loop_time(reduced) -> float:
    """Summed device seconds of the `%while` ops in a reduced trace."""
    return sum(t for name, t in reduced["op_time"].items()
               if WHILE_OP.fullmatch(name))


def read(w):
    if w.trace is None or w.peaks is None or not w.work:
        return None
    t = loop_time(w.trace)
    n_flushes = w.cache_hits + w.cache_misses
    if not t or not n_flushes:
        return None
    c = w.config
    per_tok = layer_matmul_flops(c)
    flops, nbytes = 0.0, n_flushes * layer_weight_bytes(c)
    for key, n in w.scored.items():
        ratio, quant, kv = w.work[key]
        length = kv // FL.kv_bytes_per_token(c, quant)
        f, qo = FL.attention_work(c, length)
        flops += n * (per_tok + f)
        nbytes += n * (kv + qo)
    bound = FL.decode_bound_s(flops, nbytes, w.peaks.bf16_flops,
                              w.peaks.hbm_bw)["bound_s"]
    return 100.0 * bound / t
