"""assemble_share: percent of the window the engine spent reading cache
shards and assembling the padded flush batches (np.pad, np.stack), from
the program's per-flush counters (read_s + assemble_s) summed over the
window's StageStats. A program without the counters gives no reading."""


def read(w):
    flush = [getattr(s, "flush", None) for s in w.stage_stats]
    if not flush or None in flush or not w.seconds:
        return None
    return 100.0 * sum(f.read_s + f.assemble_s for f in flush) / w.seconds
