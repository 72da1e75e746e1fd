"""h2d_pad_ratio: bytes that crossed to the device in the window's batch
loads over the real cache bytes of the tuples they carried: Σh2d_bytes of
the window's StageStats (the program's per-flush counter) over the store's
per-item bytes of the loaded tuples. It reads 1 where only the items
cross, and kv_pad_ratio where whole padded batches do. A program without
the counter, or a window that loaded no batch, gives no reading."""


def read(w):
    if w.load is None or not w.load.real:
        return None
    sent = [getattr(getattr(s, "flush", None), "h2d_bytes", None)
            for s in w.stage_stats]
    if not sent or None in sent:
        return None
    return sum(sent) / w.load.real
