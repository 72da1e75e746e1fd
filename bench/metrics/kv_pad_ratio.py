"""kv_pad_ratio: bytes of the padded batches load_batch returned in the
window over the real cache bytes of the tuples they carried (the store's
per-item metadata)."""


def read(w):
    if w.load is None or not w.load.real:
        return None
    return w.load.padded / w.load.real
