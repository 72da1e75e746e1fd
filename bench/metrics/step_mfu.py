"""step_mfu: model FLOPs of every tuple scored in the window (weights of
every layer and the head, and attention over the real cache length) over
the window's seconds times the chip's bf16 peak, in percent."""
from sbench import flops as FL


def read(w):
    if w.peaks is None or not w.work:
        return None
    c = w.config
    per_tok = FL.matmul_flops_per_token(c)
    total = 0.0
    for key, n in w.scored.items():
        ratio, quant, nbytes = w.work[key]
        length = nbytes // FL.kv_bytes_per_token(c, quant)
        total += n * (per_tok + FL.attention_work(c, length)[0])
    return 100.0 * total / (w.seconds * w.peaks.bf16_flops)
