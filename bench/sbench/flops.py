"""Operations and bytes the algorithm needs, from shapes alone.

Counted for the work a flush must do for the tuples it scores, at their
real (unpadded) cache lengths, so the number reads the same whatever
implements it. `c` is a configuration file's dict (Hugging Face keys)."""
from __future__ import annotations

from typing import Dict


def dims(c: Dict) -> Dict[str, int]:
    d = int(c["hidden_size"])
    H = int(c["num_attention_heads"])
    return {"L": int(c["num_hidden_layers"]), "d": d, "H": H,
            "KV": int(c["num_key_value_heads"]),
            "dh": int(c.get("head_dim") or d // H),
            "ff": int(c["intermediate_size"]), "V": int(c["vocab_size"])}


def kv_bytes_per_token(c: Dict, quant: bool = False) -> int:
    """Cached K and V bytes of one position over all layers: bf16, or
    int8 with one float32 scale per (position, head) for K and for V."""
    m = dims(c)
    per_head = m["dh"] + 4 if quant else 2 * m["dh"]
    return m["L"] * 2 * m["KV"] * per_head


def attention_work(c: Dict, cache_len: int, n_query: int = 1):
    """(flops, bytes) of single-query-token decode attention over a cache
    of `cache_len` real positions plus the query tokens themselves, all
    layers: QK^T and PV at 2 FLOP per multiply-add; bytes are the cached
    K/V streamed once (`cache_bytes` is added by the caller, who knows the
    rung's element width) plus the float32 queries and outputs."""
    m = dims(c)
    n = cache_len + n_query
    flops = 2.0 * 2.0 * m["H"] * m["dh"] * n * n_query * m["L"]
    qo_bytes = 2.0 * m["H"] * m["dh"] * 4 * n_query * m["L"]
    return flops, qo_bytes


def matmul_flops_per_token(c: Dict) -> float:
    """Weight FLOPs of one token through the model: every non-embedding
    projection of every layer plus the output head (the embedding is a
    gather)."""
    m = dims(c)
    d, H, KV, dh, ff = m["d"], m["H"], m["KV"], m["dh"], m["ff"]
    per_layer = d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * ff
    return 2.0 * (m["L"] * per_layer + d * m["V"])


def decode_bound_s(flops: float, nbytes: float, peak_flops: float,
                   peak_bw: float) -> Dict[str, float]:
    """The least time for `flops` and `nbytes` on a chip: the larger of
    the compute time and the memory time, and which one bounds it."""
    mem_s = nbytes / peak_bw
    compute_s = flops / peak_flops
    return {"mem_s": mem_s, "compute_s": compute_s,
            "bound_s": max(mem_s, compute_s),
            "dominant": "memory" if mem_s >= compute_s else "compute"}
