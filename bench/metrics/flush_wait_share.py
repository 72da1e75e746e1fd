"""flush_wait_share: percent of the window the engine spent blocked
forcing each flush's result to the host (the device's work, and any
copy still in flight), from the program's per-flush counters (wait_s)
summed over the window's StageStats. A program without the counters
gives no reading."""


def read(w):
    flush = [getattr(s, "flush", None) for s in w.stage_stats]
    if not flush or None in flush or not w.seconds:
        return None
    return 100.0 * sum(f.wait_s for f in flush) / w.seconds
