"""flush_compiles: XLA compiles made on the flushes' threads during the
window's flushes (persistent-cache loads included), from the program's
per-flush counters summed over the window's StageStats. A program
without the counters gives no reading."""


def read(w):
    flush = [getattr(s, "flush", None) for s in w.stage_stats]
    if not flush or None in flush:
        return None
    return sum(f.compiles for f in flush)
