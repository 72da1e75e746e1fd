"""On-disk KV-cache repository (paper §5, Fig. 4).

One *profile* = (model_name, compression ratio, optional int8
quantization). The store holds one compressed cache per (profile, item)
as an .npz shard, written once in the offline phase and memory-mapped at
query time. `load_batch` re-pads a set of items to the max compressed
length in the batch — the paper's batching scheme — and returns a
decode-ready cache pytree. It copies each item once, back to back into a
host staging buffer that the calling thread reuses from batch to batch
(`_Staging`), sends that buffer to the device unpadded, and pads it there
(`_unflatten`).

Alongside the shards, each profile directory carries an append-only
`_meta.jsonl` recording per-item byte sizes at `save` time, so batch
sizing (`ServingEngine.max_batch_for`) reads one small line instead of
decompressing a full .npz shard per flush.
"""
from __future__ import annotations

import functools
import json
import math
import os
import threading
import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.configs.base import ModelConfig
from repro.obs import FlushCounters

META_FILE = "_meta.jsonl"
# cache keys laid out (L, S', ...) per item: sent back to back along S' and
# padded on the device to the batch's S_max; every other key is stacked
# as it is
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


def _flat_len(n: int, multiple: int) -> int:
    """`n` positions rounded up to a multiple of `multiple`, and from 32
    such multiples on to one of 16 lengths per octave (less than n/16
    more), so that few distinct flat buffer shapes, and so few layout
    programs, occur."""
    step = max(multiple, 1 << max(0, n.bit_length() - 5))
    return -(-n // step) * step


@functools.partial(jax.jit, static_argnums=2)
def _unflatten(flat, spans, smax: int):
    """The padded batch from items laid back to back: (L, T, ...) ->
    (L, B, smax, ...), where item b is `spans[1, b]` positions of `flat`
    from `spans[0, b]` and every later position is exactly 0 (V's pads are
    multiplied by softmax weights of 0, so they must not be NaN). Every
    slice fits: T >= max(spans[0]) + smax."""
    rows = jax.vmap(lambda o: lax.dynamic_slice_in_dim(flat, o, smax, 1),
                    out_axes=1)(spans[0])
    keep = jnp.arange(smax)[None, :] < spans[1][:, None]
    keep = keep.reshape((1,) + keep.shape + (1,) * (flat.ndim - 2))
    return jnp.where(keep, rows, jnp.zeros((), flat.dtype))


class _Staging:
    """One reused host buffer that one cache key of a batch is assembled
    in: flat bytes, viewed as the C-contiguous (L, T, ...) items laid back
    to back along axis 1 (sequence keys), or as the (L, B, ...) items
    stacked on axis 1 (any other key).

    It grows to the largest batch it has held and never shrinks. Each item
    is copied once into place through a same-width unsigned-integer view,
    which numpy copies as plain memory whatever the dtype (bfloat16, int8,
    float32). Nothing else is written: whatever lies past the items of a
    flat buffer reaches the batch only as the zeros `_unflatten` puts in
    its place.

    `sent` is a weak reference to the device array last made from the
    buffer (weak, so that the buffer holds no device memory): an
    asynchronous host-to-device copy reads the buffer until it lands, and
    that array is ready only after it has, so `wait` blocks on it before
    the buffer is written again. An array that was donated to a
    computation is skipped: the caller forced that computation, and so the
    copy, before loading into this buffer again (the engine does, within
    two loads).
    """

    __slots__ = ("buf", "sent")

    def __init__(self):
        self.buf = np.empty(0, np.uint8)
        self.sent: Optional[weakref.ref] = None

    def wait(self) -> None:
        arr = self.sent() if self.sent is not None else None
        if arr is not None and not arr.is_deleted():
            arr.block_until_ready()

    def fill(self, items: Sequence[np.ndarray],
             offsets: Optional[Sequence[int]] = None,
             flat_len: int = 0) -> Tuple[np.ndarray, bool]:
        """`items` stacked on a new axis 1 (`offsets` None), or item i at
        `offsets[i]` along axis 1 of a (L, flat_len, ...) buffer; and
        whether the buffer had to grow."""
        a0 = items[0]
        shape = (a0.shape[0],) + ((len(items),) + a0.shape[1:]
                                  if offsets is None
                                  else (flat_len,) + a0.shape[2:])
        nbytes = math.prod(shape) * a0.dtype.itemsize
        grew = nbytes > self.buf.nbytes
        if grew:
            self.buf = np.empty(nbytes, np.uint8)
        out = self.buf[:nbytes].view(a0.dtype).reshape(shape)
        raw = out.view(f"u{a0.dtype.itemsize}")
        for i, a in enumerate(items):
            src = a.view(raw.dtype)
            if offsets is None:
                raw[:, i] = src
            else:
                raw[:, offsets[i]:offsets[i] + a.shape[1]] = src
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
        each distinct item is copied once. So the copy to the device of one
        batch may still be in flight while the next is assembled; before a
        buffer is written again, the array made from it last has to be
        ready. The returned arrays never alias a buffer.

        A sequence key (`SEQ_KEYS`) crosses to the device unpadded: its
        distinct items lie back to back in a (L, T, ...) buffer, the
        longest last, T rounded up by `_flat_len` from the end of that
        item's slice of S_max; `_unflatten` pads them into the batch on
        the flat array's device (an item repeated to round the batch up is
        sent once). Any other key (recurrent state) is stacked on the host.

        The steps run in the spans `stretto.store.read`, then
        `stretto.store.h2d` (waiting for the arrays last made from this
        turn's buffers), then per cache key `stretto.store.assemble`
        (filling its buffer), `stretto.store.h2d` (the copy to the device)
        and, for a sequence key, `stretto.store.layout` (the dispatch of
        `_unflatten`, with its compile on a new shape); with `counters`
        given, their seconds, the bytes of the padded batch
        (`padded_bytes`), the bytes sent to the device (`h2d_bytes`) and
        whether the buffers had room for the batch (`staged_reuses`) or
        one had to grow (`staged_allocs`) are added to it.
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
        # each distinct item once, in order of first appearance; slot[b]
        # is batch row b's item among them
        first: Dict[int, int] = {}
        uniq: List[Dict[str, np.ndarray]] = []
        slot: List[int] = []
        for i, s in zip(item_ids, shards):
            if i not in first:
                first[i] = len(uniq)
                uniq.append(s)
            slot.append(first[i])
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
        # per tuple of item lengths: (item offsets, flat length, spans on
        # the device)
        flats: Dict[Tuple[int, ...], Tuple[List[int], int, Any]] = {}
        assemble_s, h2d_s, layout_s = 0.0, landed.secs, 0.0
        grew, sent = False, 0
        for key, buf in zip(keys, bufs):
            if key in SEQ_KEYS:
                ns = tuple(s[key].shape[1] for s in uniq)
                if ns not in flats:
                    # the longest item last: only its slice ends past the
                    # items, by S_max less its length
                    last = ns.index(max(ns))
                    offs, end = [0] * len(ns), 0
                    for j in [j for j in range(len(ns)) if j != last] + [last]:
                        offs[j], end = end, end + ns[j]
                    flat_len = _flat_len(offs[last] + smax, pad_to_multiple)
                    # `_unflatten` slices S_max from every offset, and
                    # dynamic_slice would clamp one that ran past T
                    assert ns[last] <= smax <= flat_len - offs[last], \
                        (ns, smax, flat_len)
                    spans = np.array([[offs[j] for j in slot],
                                      [ns[j] for j in slot]], np.int32)
                    with obs.span("stretto.store.h2d", key="spans") as h2d:
                        flats[ns] = (offs, flat_len, jnp.asarray(spans))
                    h2d_s += h2d.secs
                    sent += spans.nbytes
                offs, flat_len, spans_dev = flats[ns]
                with obs.span("stretto.store.assemble", key=key) as assemble:
                    host, g = buf.fill([s[key] for s in uniq], offs, flat_len)
                with obs.span("stretto.store.h2d", key=key) as h2d:
                    flat = jnp.asarray(host)
                with obs.span("stretto.store.layout", key=key) as layout:
                    cache[key] = _unflatten(flat, spans_dev, smax)
                layout_s += layout.secs
                del flat    # the batch is all the device keeps
            else:
                with obs.span("stretto.store.assemble", key=key) as assemble:
                    host, g = buf.fill([s[key] for s in shards])
                with obs.span("stretto.store.h2d", key=key) as h2d:
                    cache[key] = jnp.asarray(host)
                    if cache[key].device.platform == "cpu":
                        # the CPU adopts an aligned host buffer as the
                        # array's own memory: give it one that is never
                        # refilled
                        cache[key] = jnp.asarray(host.copy())
            buf.sent = weakref.ref(cache[key])
            assemble_s += assemble.secs
            h2d_s += h2d.secs
            grew |= g
            sent += host.nbytes
        with obs.span("stretto.store.h2d", key="lengths") as h2d:
            cache["lengths"] = jnp.asarray(lengths)
        if counters is not None:
            counters.read_s += read.secs
            counters.assemble_s += assemble_s
            counters.h2d_s += h2d_s + h2d.secs
            counters.layout_s += layout_s
            counters.padded_bytes += sum(int(v.nbytes) for k, v in
                                         cache.items() if k != "lengths")
            counters.h2d_bytes += sent + lengths.nbytes
            counters.staged_allocs += int(grew)
            counters.staged_reuses += int(not grew)
        return cache, lengths
