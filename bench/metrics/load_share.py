"""load_share: percent of the window spent inside CacheStore.load_batch
(shard reads, np.pad, np.stack and the copy to the device), from the
benchmark's span around it."""


def read(w):
    if w.load is None or not w.load.calls:
        return None
    return 100.0 * w.load.secs / w.seconds
