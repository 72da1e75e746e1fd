"""decode_query_attention_roofline: the least time the chip needs for the
decode attention of every tuple scored in the window (FLOPs over the
bf16 peak or the real cached K/V bytes over HBM bandwidth, whichever is
larger), over the summed device time of the Pallas decode kernel in the
trace, told from other custom calls by its operand and result shapes, in
percent. Real cache lengths and bytes come from the store's
per-item metadata, so padding counts as waste, not work."""
from sbench import flops as FL
from sbench import trace as TRC



def kernel_pattern(c) -> str:
    """The signature of `repro.kernels.decode_attention`'s Pallas kernel
    at this configuration's widths: lengths s32[B], the window s32[1],
    query rows [B, KV, R, dh], the K and V caches [B, S, KV, dh] (then
    the int8 scales, if any), and the output [B, KV, R, dh]."""
    kv, dh = c["num_key_value_heads"], c["head_dim"]
    return (rf"tpu_custom_call\(s32\[(\d+)\],s32\[1\],"
            rf"\w+\[\1,{kv},(\d+),{dh}\],\w+\[\1,(\d+),{kv},{dh}\]"
            rf".*\)->\w+\[\1,{kv},\2,{dh}\]$")


def read(w):
    if w.trace is None or w.peaks is None or not w.work:
        return None
    c = w.config
    t = TRC.kernel_time(w.trace, kernel_pattern(c))
    if not t:
        return None
    flops = nbytes = 0.0
    for key, n in w.scored.items():
        ratio, quant, kv = w.work[key]
        length = kv // FL.kv_bytes_per_token(c, quant)
        f, qo = FL.attention_work(c, length)
        flops += n * f
        nbytes += n * (kv + qo)
    bound = FL.decode_bound_s(flops, nbytes, w.peaks.bf16_flops,
                              w.peaks.hbm_bw)["bound_s"]
    return 100.0 * bound / t
