"""dev_cache_hit_share: percent of the window's flush batches served from
the engine's device-resident LRU (its hit and miss counters)."""


def read(w):
    n = w.cache_hits + w.cache_misses
    return 100.0 * w.cache_hits / n if n else None
