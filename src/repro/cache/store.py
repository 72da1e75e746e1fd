"""On-disk KV-cache repository (paper §5, Fig. 4).

One *profile* = (model_name, compression ratio, optional int8
quantization). The store holds one compressed cache per (profile, item)
as an .npz shard, written once in the offline phase and memory-mapped at
query time. `load_batch` re-pads a set of items to the max compressed
length in the batch — the paper's batching scheme — and returns a
decode-ready cache pytree. It copies each item once, into its slot of a
host staging buffer that the calling thread reuses from batch to batch
(`_Staging`), and hands that buffer to the device.

Alongside the shards, each profile directory carries an append-only
`_meta.jsonl` recording per-item byte sizes at `save` time, so batch
sizing (`ServingEngine.max_batch_for`) reads one small line instead of
decompressing a full .npz shard per flush.
"""
from __future__ import annotations

import json
import math
import os
import threading
import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.obs import FlushCounters

META_FILE = "_meta.jsonl"
# cache keys laid out (L, S', ...) per item: padded along S' to the batch's
# S_max; every other key is stacked as it is
SEQ_KEYS = frozenset({"k", "v", "c_kv", "k_rope", "k_scale", "v_scale"})


@dataclass(frozen=True)
class Profile:
    model_name: str
    ratio: float
    quant: bool = False

    @property
    def tag(self) -> str:
        base = f"{self.model_name}__r{int(round(self.ratio * 100)):02d}"
        return base + ("__q8" if self.quant else "")


class _Staging:
    """One reused host buffer that a batch of one cache key is assembled
    in: flat bytes, viewed as the C-contiguous (L, B, S_max, ...) batch.

    It grows to the largest batch it has held and never shrinks. Each item
    is copied once into its slot through a same-width unsigned-integer
    view, which numpy copies as plain memory whatever the dtype (bfloat16,
    int8, float32). Pad positions read 0: while the batch's shape is
    unchanged only `[n_i, high_i)` of slot i is zeroed, the part a longer
    earlier item left; any other shape zeroes the whole pad.

    `sent` is a weak reference to the device array last copied from the
    buffer (weak, so that the buffer holds no device memory): an
    asynchronous host-to-device copy reads the buffer until it lands, so
    `wait` blocks on it before the buffer is written again. An
    array that was donated to a computation is skipped: the caller forced
    that computation, and so the copy, before loading into this buffer
    again (the engine does, within two loads).
    """

    __slots__ = ("buf", "layout", "high", "sent")

    def __init__(self):
        self.buf = np.empty(0, np.uint8)
        self.layout: Optional[Tuple] = None
        self.high: List[int] = []
        self.sent: Optional[weakref.ref] = None

    def wait(self) -> None:
        arr = self.sent() if self.sent is not None else None
        if arr is not None and not arr.is_deleted():
            arr.block_until_ready()

    def fill(self, items: Sequence[np.ndarray],
             smax: Optional[int]) -> Tuple[np.ndarray, bool]:
        """The batch of `items`, padded along axis 1 to `smax` (None:
        stacked unpadded), and whether the buffer had to grow."""
        a0 = items[0]
        shape = (a0.shape[0], len(items)) + (
            a0.shape[1:] if smax is None else (smax,) + a0.shape[2:])
        nbytes = math.prod(shape) * a0.dtype.itemsize
        grew = nbytes > self.buf.nbytes
        if grew:
            self.buf = np.empty(nbytes, np.uint8)
        layout = (shape, a0.dtype)
        if grew or layout != self.layout:
            self.high = [shape[2] if smax is not None else 0] * len(items)
        # unset until every slot is written, so a fill that raises leaves
        # the next one to zero the whole pad
        self.layout = None
        out = self.buf[:nbytes].view(a0.dtype).reshape(shape)
        raw = out.view(f"u{a0.dtype.itemsize}")
        for i, a in enumerate(items):
            src = a.view(raw.dtype)
            if smax is None:
                raw[:, i] = src
                continue
            n = a.shape[1]
            raw[:, i, :n] = src
            if self.high[i] > n:
                raw[:, i, n:self.high[i]] = 0
            self.high[i] = n
        self.layout = layout
        return out, grew


class CacheStore:
    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._mem: Dict[Tuple[str, int], Dict[str, np.ndarray]] = {}
        # per-profile {item_id: nbytes}, mirrored in _meta.jsonl on disk
        self._meta: Dict[str, Dict[int, int]] = {}
        # monotonic telemetry: bytes of cached KV arrays handed to decode
        # batches. The global counter is the store-wide total; the
        # thread-local twin counts only bytes loaded by the calling thread,
        # which is what the runtime's StageStats reads deltas of — each
        # stage flush runs entirely on one dispatcher thread, so
        # thread-local deltas stay exact when flushes overlap (the global
        # counter's deltas would double-count concurrent loads)
        self.bytes_loaded = 0
        self._tl = threading.local()
        self._bytes_lock = threading.Lock()

    @property
    def bytes_loaded_local(self) -> int:
        """KV bytes materialized by the *calling thread* (monotonic)."""
        return getattr(self._tl, "bytes_loaded", 0)

    def _path(self, profile: Profile, item_id: int) -> str:
        d = os.path.join(self.root, profile.tag)
        return os.path.join(d, f"{item_id}.npz")

    def _meta_path(self, profile: Profile) -> str:
        return os.path.join(self.root, profile.tag, META_FILE)

    def save(self, profile: Profile, item_id: int,
             arrays: Dict[str, np.ndarray], length: int):
        path = self._path(profile, item_id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        arrs = {k: np.asarray(v) for k, v in arrays.items()}
        # .npz keeps only numpy's own dtypes: a bfloat16 array comes back
        # as raw 2-byte voids, so every array's dtype is stored beside it
        # and restored on load
        dtypes = json.dumps({k: a.dtype.name for k, a in arrs.items()})
        np.savez(path, __length__=np.int32(length),
                 __dtypes__=np.asarray(dtypes), **arrs)
        self._mem[(profile.tag, item_id)] = {
            "__length__": np.int32(length), **arrs}
        nbytes = sum(a.nbytes for a in arrs.values())
        with open(self._meta_path(profile), "a") as f:
            f.write(json.dumps({"id": item_id, "nbytes": nbytes,
                                "length": int(length)}) + "\n")
        self._meta.setdefault(profile.tag, {})[item_id] = nbytes

    def _load_meta(self, profile: Profile) -> Dict[int, int]:
        """Per-item nbytes for a profile; last write wins (append-only)."""
        if profile.tag not in self._meta:
            meta: Dict[int, int] = {}
            p = self._meta_path(profile)
            if os.path.exists(p):
                with open(p) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        rec = json.loads(line)
                        meta[int(rec["id"])] = int(rec["nbytes"])
            self._meta[profile.tag] = meta
        return self._meta[profile.tag]

    def item_nbytes(self, profile: Profile,
                    item_id: Optional[int] = None) -> Optional[int]:
        """Cache bytes for one stored item (any item if id is None),
        served from profile metadata — no shard decompression. Falls back
        to loading the shard for stores written before metadata existed."""
        meta = self._load_meta(profile)
        if item_id is None:
            if meta:
                return next(iter(meta.values()))
            item_id = self.any_item_id(profile)
            if item_id is None:
                return None
        if item_id in meta:
            return meta[item_id]
        if not self.has(profile, item_id):
            return None
        shard = self.load(profile, item_id)
        nbytes = sum(a.nbytes for k, a in shard.items()
                     if k != "__length__")
        meta[item_id] = nbytes
        return nbytes

    def load(self, profile: Profile, item_id: int) -> Dict[str, np.ndarray]:
        key = (profile.tag, item_id)
        if key not in self._mem:
            with np.load(self._path(profile, item_id)) as z:
                shard = {k: z[k] for k in z.files}
            dtypes = json.loads(str(shard.pop("__dtypes__", "{}")))
            for k, name in dtypes.items():
                shard[k] = shard[k].view(jnp.dtype(name))
            self._mem[key] = shard
        return self._mem[key]

    def has(self, profile: Profile, item_id: int) -> bool:
        return ((profile.tag, item_id) in self._mem
                or os.path.exists(self._path(profile, item_id)))

    def any_item_id(self, profile: Profile) -> Optional[int]:
        """Any stored item id for this profile (None if nothing stored);
        used to measure per-item cache bytes for batch sizing."""
        for tag, item_id in self._mem:
            if tag == profile.tag:
                return item_id
        d = os.path.join(self.root, profile.tag)
        if os.path.isdir(d):
            for f in os.listdir(d):
                if f.endswith(".npz"):
                    return int(f[:-len(".npz")])
        return None

    def storage_bytes(self, profile: Profile) -> int:
        d = os.path.join(self.root, profile.tag)
        if not os.path.isdir(d):
            return 0
        return sum(os.path.getsize(os.path.join(d, f))
                   for f in os.listdir(d) if f.endswith(".npz"))

    def load_batch(self, cfg: ModelConfig, profile: Profile,
                   item_ids: Sequence[int], pad_to_multiple: int = 32,
                   headroom: int = 0, n_real: Optional[int] = None,
                   counters: Optional[FlushCounters] = None
                   ) -> Tuple[Dict[str, Any], np.ndarray]:
        """Assemble a right-padded decode cache for a batch of items.

        Returns (cache pytree with leaves (L, B, S_max, ...) + 'lengths',
        lengths array). Padding to the max compressed length in the batch
        is the paper's execution-time batching scheme. `headroom` reserves
        slots for the operator query + generated tokens. For quantized
        profiles the shards carry int8 k/v plus (L, S', KV) float32
        scales; scales pad along S like the caches so the decode kernel's
        grid stays aligned.

        `n_real` bounds the bytes-loaded telemetry to the first n_real
        entries: callers that replicate an item to round the batch up to
        a shape bucket (see ServingEngine) pass the un-padded count, so
        the counter measures the cache bytes the *scored tuples* needed —
        an exact quantity independent of how flushes were grouped — not
        the padding replicas.

        Each cache key's batch is assembled in one of two host staging
        buffers per key that the calling thread uses in turn (`_Staging`):
        each item is copied once, into its slot. So the copy to the device
        of one batch may still be in flight while the next is assembled;
        before a buffer is written again, the copy made from it last has
        to land. The returned arrays never alias a buffer.

        The steps run in the spans `stretto.store.read`, then
        `stretto.store.h2d` (waiting for the copies out of this turn's
        buffers to land), then per cache key `stretto.store.assemble`
        (filling its buffer) and `stretto.store.h2d` (the copy to the
        device); with `counters` given, their seconds, the bytes of the
        padded batch and whether the buffers had room for it
        (`staged_reuses`) or one had to grow (`staged_allocs`) are added
        to it.
        """
        with obs.span("stretto.store.read") as read:
            shards = [self.load(profile, i) for i in item_ids]
        n_count = len(shards) if n_real is None else min(n_real, len(shards))
        loaded = sum(a.nbytes for s in shards[:n_count]
                     for k, a in s.items() if k != "__length__")
        with self._bytes_lock:
            self.bytes_loaded += loaded
        self._tl.bytes_loaded = self.bytes_loaded_local + loaded
        lengths = np.array([int(s["__length__"]) for s in shards], np.int32)
        smax = int(lengths.max()) + headroom
        smax = ((smax + pad_to_multiple - 1) // pad_to_multiple
                * pad_to_multiple)
        keys = [k for k in shards[0] if k != "__length__"]
        turn = getattr(self._tl, "turn", 0)
        self._tl.turn = 1 - turn
        staging = self._tl.__dict__.setdefault("staging", {})
        bufs = [staging.setdefault(k, (_Staging(), _Staging()))[turn]
                for k in keys]
        with obs.span("stretto.store.h2d", key="staging") as landed:
            for buf in bufs:
                buf.wait()
        cache: Dict[str, Any] = {}
        assemble_s, h2d_s, grew = 0.0, landed.secs, False
        for key, buf in zip(keys, bufs):
            with obs.span("stretto.store.assemble", key=key) as assemble:
                batch, g = buf.fill([s[key] for s in shards],
                                    smax if key in SEQ_KEYS else None)
            with obs.span("stretto.store.h2d", key=key) as h2d:
                cache[key] = jnp.asarray(batch)
                if cache[key].device.platform == "cpu":
                    # the CPU adopts an aligned host buffer as the array's
                    # own memory: give it one that is never refilled
                    cache[key] = jnp.asarray(batch.copy())
            buf.sent = weakref.ref(cache[key])
            assemble_s += assemble.secs
            h2d_s += h2d.secs
            grew |= g
        with obs.span("stretto.store.h2d", key="lengths") as h2d:
            cache["lengths"] = jnp.asarray(lengths)
        if counters is not None:
            counters.read_s += read.secs
            counters.assemble_s += assemble_s
            counters.h2d_s += h2d_s + h2d.secs
            counters.padded_bytes += sum(int(v.nbytes) for k, v in
                                         cache.items() if k != "lengths")
            counters.staged_allocs += int(grew)
            counters.staged_reuses += int(not grew)
        return cache, lengths
