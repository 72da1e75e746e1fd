"""lru_evictions: device-LRU entries that the window's flush loads pushed
out to stay within the engine's memory budget, from the program's
per-flush counters (lru_evictions) summed over the window's StageStats.
0 in a window whose working set stays on the device. A program without
the counter gives no reading."""


def read(w):
    counts = [getattr(getattr(s, "flush", None), "lru_evictions", None)
              for s in w.stage_stats]
    if not counts or None in counts:
        return None
    return sum(counts)
