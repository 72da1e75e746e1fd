"""flush_width: tuples per engine flush over the window, from the
runtime's per-stage counters (sum n_tuples / sum n_batches)."""


def read(w):
    n = sum(s.n_tuples for s in w.stage_stats)
    b = sum(s.n_batches for s in w.stage_stats)
    return n / b if b else None
