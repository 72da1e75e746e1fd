"""Prefill-skip batched serving engine (paper §5, Fig. 4).

Offline: `build_profiles` prefetches every corpus item through each model
once, compresses the KV cache at each ladder ratio (Expected Attention),
optionally quantizes rungs to int8, and persists the profiles in the
CacheStore.

Online: `run_filter` / `run_map` load a profile's caches for a batch of
items, pad to the max compressed length, *skip prefill entirely*, feed the
operator query tokens through the decode path, and read out answer-token
log-odds ('1' vs '0') or a greedy value token + confidence margin.

The decode path is the Pallas fast path:
  - the attention backend is selectable (`kernels` ctor arg, else the
    STRETTO_KERNELS env var: auto | pallas | interpret | ref);
  - by default the operator query is fed through ONE fused multi-token
    attention dispatch per flush (`decode_multi`) instead of a per-token
    lax.scan (`fused` ctor arg, else STRETTO_FUSED; scan remains the
    fallback for archs with recurrent state);
  - repeated flushes against the same (profile, batch) skip the
    npz-reload + re-pad + H2D copy via a device-resident LRU cache
    bounded by `memory_budget_bytes` (`device_cache` ctor arg, else
    STRETTO_DEVICE_CACHE). Device-cache hits do NOT increment the
    kv_bytes telemetry — it counts real loads only.

Transfers overlap compute (`async_h2d` ctor arg, else STRETTO_ASYNC_H2D):
a multi-batch run_filter/run_map dispatches the decode for batch i and
loads + H2D-copies batch i+1's KV caches *before* forcing batch i's
logits, so the transfer can hide behind the accelerator's decode; the
host time of that load is counted into `prefetch_s` (an upper bound on
the time hidden: only a device trace shows what the device actually
covered). On the same flag (and only when the device-resident LRU is
off, which would need the buffers again) the jitted decode donates the
consumed cache buffers back to XLA via donate_argnums, so the next
batch's caches can reuse that HBM instead of peaking at 2x; donated
bytes are counted into `donated_bytes`.

Telemetry: every flush runs inside `stretto.engine.*` spans (flush, load,
lookup, dispatch, prefetch, wait; `CacheStore.load_batch` adds read,
assemble, h2d and layout) and adds to the calling thread's
`FlushCounters` (`transfer_stats_local`): read, assemble,
host-to-device, layout and wait seconds, prefetch seconds, XLA compiles,
padded, sent and donated bytes, the bytes served from the device LRU and
the entries its loads evicted. The counters are per thread, so the
runtime's per-flush StageStats deltas stay exact under concurrent
dispatch.

Multi-device placement: `place_on(device)` pins the calling thread's
flushes — params (device_put once per device, memoized) and decode
computation — onto one device; `default_device` (EngineSpec placement)
does the same engine-wide. The runtime's MeshDispatcher enters
`place_on` per corpus shard to scatter the cascade over a jax mesh.

Batch size is memory-bounded: higher compression -> smaller caches ->
larger batches -> fewer calls (the paper's batching speedup mechanism).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# CPU (and some accelerator) buffers cannot always be donated; jax warns
# per compilation. Donation here is best-effort HBM reuse — a backend
# that cannot honor it silently falls back to copying, which is exactly
# the pre-donation behavior, so the warning is noise in CPU CI runs.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from repro import obs
from repro.cache.compression import (QueryStats, calibrate_query_stats,
                                     compress_item_cache, quantize_kv)
from repro.cache.store import CacheStore, Profile
from repro.configs.base import ModelConfig
from repro.kernels import ops as KOPS
from repro.models import (decode_multi, decode_step, init_cache, prefill,
                          supports_fused_decode)

# Engine loads pad the cache length to a multiple of the Pallas block so
# the kernel grid is always legal (S % block_s == 0), whichever backend
# ends up selected. Padded positions are masked exactly, and kv_bytes
# counts pre-padding bytes, so this changes neither results nor telemetry.
KERNEL_BLOCK_S = 128


def _env_flag(name: str, default: bool = True) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v not in ("0", "false", "False", "no")


@dataclass
class EngineModel:
    cfg: ModelConfig
    params: Any
    stats: Optional[QueryStats] = None


class ServingEngine:
    """Executes semantic operators over precomputed KV-cache profiles."""

    def __init__(self, store: CacheStore,
                 memory_budget_bytes: float = 2e9,
                 max_batch: int = 128,
                 kernels: Optional[str] = None,
                 fused: Optional[bool] = None,
                 device_cache: Optional[bool] = None,
                 async_h2d: Optional[bool] = None):
        self.store = store
        self.models: Dict[str, EngineModel] = {}
        self.memory_budget = memory_budget_bytes
        self.max_batch = max_batch
        # attention backend: explicit arg > STRETTO_KERNELS env > auto.
        # Validated (and env read) at flush time, not here, so tests can
        # flip the env var between flushes.
        self.kernels = kernels
        self.fused = (_env_flag("STRETTO_FUSED") if fused is None
                      else bool(fused))
        self.device_cache = (_env_flag("STRETTO_DEVICE_CACHE")
                             if device_cache is None else bool(device_cache))
        self.async_h2d = (_env_flag("STRETTO_ASYNC_H2D")
                          if async_h2d is None else bool(async_h2d))
        self._decode_jit: Dict[Tuple[str, bool, str, bool], Any] = {}
        # engine-wide device pin (EngineSpec(device=...)); place_on()
        # overrides it per thread (MeshDispatcher shard placement)
        self.default_device: Optional[Any] = None
        self._placement_tl = threading.local()
        # params placed per device, once: (model_name, device id) ->
        # device_put params pytree
        self._placed_params: Dict[Tuple[str, Any], Any] = {}
        self._placed_lock = threading.Lock()
        # per-thread FlushCounters: the runtime's StageStats deltas read
        # the calling thread's set, so overlapping flushes never
        # interleave into each other's deltas
        self._flush_tl = threading.local()
        # device-resident profile cache: (profile.tag, ids, headroom,
        # device id) -> (cache pytree on that device, nbytes); the device
        # is part of the key so a batch placed on one device is never
        # handed to a flush placed on another. One lock serializes
        # lookup-or-load so concurrent flushes of the same key load once
        # and total kv_bytes stays schedule-independent.
        self._dev_cache: "OrderedDict[Tuple, Tuple[Dict[str, Any], int]]" \
            = OrderedDict()
        self._dev_bytes = 0
        self._dev_lock = threading.Lock()
        self.dev_cache_hits = 0
        self.dev_cache_misses = 0
        # telemetry for the fused-path acceptance hook: number of
        # attention decode dispatches issued (1 per fused flush,
        # len(query) per scan flush)
        self.attn_dispatches = 0

    # ---------------- placement + transfer telemetry ----------------

    @contextlib.contextmanager
    def place_on(self, device, sharding=None):
        """Pin this thread's flushes onto `device`: params are device_put
        there (once, memoized) and the decode computation runs there.
        `sharding` optionally carries a NamedSharding for the params
        (resolved through the logical-axis rules); default is plain
        single-device placement. Nests/restores like a context var."""
        tl = self._placement_tl
        prev = getattr(tl, "placement", None)
        tl.placement = (device, sharding)
        try:
            yield
        finally:
            tl.placement = prev

    def _placement(self) -> Optional[Tuple[Any, Any]]:
        got = getattr(self._placement_tl, "placement", None)
        if got is not None:
            return got
        if self.default_device is not None:
            return (self.default_device, None)
        return None

    def _device_ctx(self, placement):
        return (contextlib.nullcontext() if placement is None
                else jax.default_device(placement[0]))

    def _params_for(self, em: EngineModel, model_name: str, placement):
        """The model params on the placement's device (device_put once
        per (model, device); unplaced engines use the params as-is)."""
        if placement is None:
            return em.params
        dev, sharding = placement
        key = (model_name, getattr(dev, "id", dev))
        with self._placed_lock:
            got = self._placed_params.get(key)
            if got is None:
                got = jax.device_put(
                    em.params, sharding if sharding is not None else dev)
                self._placed_params[key] = got
            return got

    def _counters(self) -> obs.FlushCounters:
        """The calling thread's live counter set (mutated in place)."""
        tl = self._flush_tl
        if not hasattr(tl, "counters"):
            tl.counters = obs.FlushCounters()
        return tl.counters

    def transfer_stats_local(self) -> obs.FlushCounters:
        """A snapshot of the calling thread's monotonic FlushCounters —
        the runtime's run_operator takes before/after deltas."""
        return dataclasses.replace(self._counters())

    @contextlib.contextmanager
    def _counting_compiles(self):
        """Add the XLA compiles made on this thread inside the block to
        its counters."""
        n0, s0 = obs.compiles_here()
        try:
            yield
        finally:
            n1, s1 = obs.compiles_here()
            c = self._counters()
            c.compiles += n1 - n0
            c.compile_s += s1 - s0

    # ---------------- offline phase ----------------

    def register_model(self, name: str, cfg: ModelConfig, params):
        self.models[name] = EngineModel(cfg, params)

    def build_profiles(self, model_name: str, items: Sequence[Any],
                       ratios: Sequence[float], prefill_batch: int = 16,
                       quant_ratios: Sequence[float] = ()):
        """Prefill every item once, compress at every ratio, persist.

        `quant_ratios` adds int8 rungs: the cache is compressed at the
        given ratio and then quantized to int8 with per-token scales
        (halved HBM traffic at decode time), stored under a distinct
        quant profile tag.

        Each step runs in its span: `stretto.offline.prefill` (the
        calibration prefill too), `stretto.offline.compress` (one ratio of
        one item), `stretto.offline.quantize` (one int8 rung of one item:
        compression and quantization) and `stretto.offline.save`.
        """
        em = self.models[model_name]
        cfg = em.cfg
        has_cache = cfg.attn_kind != "rwkv6"
        if quant_ratios and cfg.attn_kind not in ("gqa", "hymba"):
            raise ValueError(
                f"int8 KV profiles require a k/v cache; "
                f"attn_kind={cfg.attn_kind!r} has none")
        # calibration on the first few items
        if has_cache and em.stats is None:
            with obs.span("stretto.offline.prefill", op=model_name,
                          calibrate=1):
                calib = _pad_tokens([it.tokens for it in items[:8]])
                em.stats = calibrate_query_stats(em.params, cfg,
                                                 tokens=calib)
                jax.block_until_ready(em.stats)
        for start in range(0, len(items), prefill_batch):
            chunk = items[start:start + prefill_batch]
            with obs.span("stretto.offline.prefill", op=model_name,
                          items=len(chunk)):
                toks = _pad_tokens([it.tokens for it in chunk])
                lengths = jnp.asarray([len(it.tokens) for it in chunk],
                                      jnp.int32)
                _, cache = prefill(em.params, cfg, tokens=toks,
                                   max_len=toks.shape[1], lengths=lengths)
                jax.block_until_ready(cache)
            for bi, it in enumerate(chunk):
                item_cache = jax.tree.map(_take_item(bi), cache)
                n = int(lengths[bi])
                for ratio in ratios:
                    if not has_cache and ratio > 0:
                        continue     # rwkv6: no ladder (DESIGN.md)
                    with obs.span("stretto.offline.compress", op=model_name,
                                  ratio=ratio):
                        if has_cache:
                            arrays, new_len = compress_item_cache(
                                cfg, item_cache, em.stats, ratio, n)
                        else:
                            arrays = {k: np.asarray(v[:, 0]) for k, v in
                                      item_cache.items() if k != "lengths"}
                            new_len = n
                    with obs.span("stretto.offline.save", op=model_name):
                        self.store.save(Profile(model_name, ratio),
                                        it.item_id, arrays, new_len)
                for ratio in quant_ratios:
                    with obs.span("stretto.offline.quantize", op=model_name,
                                  ratio=ratio):
                        arrays, new_len = compress_item_cache(
                            cfg, item_cache, em.stats, ratio, n)
                        arrays = quantize_kv(arrays)
                    with obs.span("stretto.offline.save", op=model_name):
                        self.store.save(Profile(model_name, ratio, quant=True),
                                        it.item_id, arrays, new_len)

    # ---------------- online phase ----------------

    def max_batch_for(self, model_name: str, ratio: float,
                      item_id: Optional[int] = None,
                      quant: bool = False) -> int:
        """Memory-bounded max decode batch for a (model, ratio) profile.

        Higher compression -> smaller per-item caches -> larger batches ->
        fewer calls: the paper's batching speedup mechanism (§5), exposed
        so the planner's batch-size-aware cost model can exploit the
        compression -> batch-size link. Per-item bytes come from the
        store's profile metadata (recorded at save time — no shard read
        on the flush path); never exceeds `max_batch`. Falls back to
        `max_batch` when the profile has no stored shards yet.
        """
        profile = Profile(model_name, ratio, quant)
        per_item = self.store.item_nbytes(profile, item_id)
        if per_item is None:
            return self.max_batch
        b = max(1, int(self.memory_budget / max(per_item, 1)))
        return min(b, self.max_batch)

    def _batch_size(self, profile: Profile, item_ids) -> int:
        b = self.max_batch_for(profile.model_name, profile.ratio,
                               item_ids[0], quant=profile.quant)
        return min(b, len(item_ids))

    def _decode_fn(self, model_name: str, fused: bool, backend: str,
                   donate: bool = False):
        key = (model_name, fused, backend, donate)
        if key not in self._decode_jit:
            em = self.models[model_name]

            # the flush program's ops carry the scope "stretto.flush" in
            # their metadata (and XLA names some instructions after it)
            if fused:
                def run_tokens(params, cache, tokens):
                    """All query tokens in ONE fused attention dispatch."""
                    with jax.named_scope("stretto.flush"):
                        return decode_multi(params, em.cfg, cache,
                                            tokens=tokens, kernels=backend)
            else:
                def run_tokens(params, cache, tokens):
                    """Feed tokens (B, L) sequentially; return final
                    logits."""
                    def step(cache, tok):
                        logits, cache = decode_step(params, em.cfg, cache,
                                                    tokens=tok[:, None],
                                                    kernels=backend)
                        return cache, logits
                    with jax.named_scope("stretto.flush"):
                        cache, logits_seq = jax.lax.scan(
                            step, cache, jnp.moveaxis(tokens, 1, 0))
                    return logits_seq[-1], cache

            # donate the consumed cache buffers (arg 1) so XLA reuses
            # their HBM for the next batch instead of peaking at 2x —
            # only ever requested when nothing else holds the buffers
            # (device-resident LRU off, prefetched caches used once)
            self._decode_jit[key] = jax.jit(
                run_tokens, donate_argnums=(1,) if donate else ())
        return self._decode_jit[key]

    def device_cache_clear(self):
        with self._dev_lock:
            self._dev_cache.clear()
            self._dev_bytes = 0

    def warm(self, model_name: str, ratio: float, item_ids: Sequence[int],
             query_len: int = 1, quant: bool = False) -> int:
        """Pre-stage a profile's flush batches in the device-resident LRU
        (scheduler keep_warm tenants): loads each memory-bounded batch of
        `item_ids` through the same `_load_for` path a flush would take,
        so subsequent flushes over the same id runs hit the LRU instead
        of reloading + H2D-copying. `query_len` must match the operator
        query length the flushes will use (semantic filter/map operators
        send a single query token). Returns the number of batches staged;
        a no-op (0) when the device cache is off, the model is unknown,
        or the profile has no stored shards yet — warming is best-effort
        and never a correctness dependency."""
        if not self.device_cache or model_name not in self.models \
                or not item_ids:
            return 0
        em = self.models[model_name]
        profile = Profile(model_name, ratio, quant)
        # a cold-started engine (e.g. a remote worker warmed before its
        # first corpus sync) may hold none — or only some — of the ids
        # for this rung: stage what exists, skip the rest. Probing only
        # the first id would crash the load below whenever the rung is
        # partially built.
        ids = [int(i) for i in item_ids if self.store.has(profile, i)]
        if not ids:
            return 0                     # rung not built (yet): no-op
        bs = self._batch_size(profile, ids)
        query_tokens = [0] * max(int(query_len), 1)
        n = 0
        for s in range(0, len(ids), bs):
            with self._device_ctx(self._placement()):
                self._load_for(em, profile, ids[s:s + bs], query_tokens, bs)
            n += 1
        return n

    def evict(self, model_name: Optional[str] = None,
              ratio: Optional[float] = None,
              quant: bool = False) -> int:
        """Drop device-LRU entries for a profile (scheduler cold-tier
        release): `model_name=None` clears everything, `ratio=None`
        drops every rung of the model, otherwise exactly the
        (model, ratio, quant) profile. Returns entries dropped. Only the
        device-resident copies go — the on-disk profiles are untouched,
        so the next flush simply reloads."""
        with self._dev_lock:
            if model_name is None:
                n = len(self._dev_cache)
                self._dev_cache.clear()
                self._dev_bytes = 0
                return n
            if ratio is None:
                prefix = f"{model_name}__r"
                keys = [k for k in self._dev_cache
                        if k[0].startswith(prefix)]
            else:
                tag = Profile(model_name, ratio, quant).tag
                keys = [k for k in self._dev_cache if k[0] == tag]
            for k in keys:
                _, nbytes = self._dev_cache.pop(k)
                self._dev_bytes -= nbytes
            return len(keys)

    def _load_cached(self, em: EngineModel, profile: Profile,
                     ids: Sequence[int], headroom: int, n_real: int):
        """load_batch through the device-resident LRU (kv_bytes counts
        real loads only — a hit skips the npz-reload + re-pad + H2D copy
        entirely), in the span `stretto.engine.load`. The LRU lookup runs
        in `stretto.engine.lookup` (argument `hit` 1 or 0; a miss's load
        follows it); a hit adds the batch's bytes to `lru_hit_bytes`, and
        each entry a miss pushes out counts in `lru_evictions`."""
        with obs.span("stretto.engine.load", tuples=n_real):
            return self._lookup_or_load(em, profile, ids, headroom, n_real)

    def _lookup_or_load(self, em: EngineModel, profile: Profile,
                        ids: Sequence[int], headroom: int, n_real: int):
        if not self.device_cache:
            cache, _ = self.store.load_batch(
                em.cfg, profile, ids, pad_to_multiple=KERNEL_BLOCK_S,
                headroom=headroom, n_real=n_real,
                counters=self._counters())
            return cache
        placement = self._placement()
        dev = None if placement is None else getattr(placement[0], "id",
                                                     placement[0])
        key = (profile.tag, tuple(ids), headroom, dev)
        counters = self._counters()
        with self._dev_lock:
            hit = self._dev_cache.get(key)
            with obs.span("stretto.engine.lookup", hit=int(hit is not None)):
                if hit is not None:
                    self._dev_cache.move_to_end(key)
                    self.dev_cache_hits += 1
                    counters.lru_hit_bytes += sum(
                        int(v.nbytes) for k, v in hit[0].items()
                        if k != "lengths")
                    return hit[0]
            self.dev_cache_misses += 1
            cache, _ = self.store.load_batch(
                em.cfg, profile, ids, pad_to_multiple=KERNEL_BLOCK_S,
                headroom=headroom, n_real=n_real, counters=counters)
            nbytes = sum(np.asarray(v).nbytes if not hasattr(v, "nbytes")
                         else v.nbytes for v in cache.values())
            self._dev_cache[key] = (cache, nbytes)
            self._dev_bytes += nbytes
            while self._dev_bytes > self.memory_budget \
                    and len(self._dev_cache) > 1:
                _, (_, old_bytes) = self._dev_cache.popitem(last=False)
                self._dev_bytes -= old_bytes
                counters.lru_evictions += 1
            return cache

    def _load_for(self, em: EngineModel, profile: Profile, ids: List[int],
                  query_tokens: Sequence[int], bs: int):
        """Load (or device-cache-hit) one flush batch's caches — the same
        padded shape `_flush` would load, so a prefetched cache slots in
        as `preloaded` bit-for-bit."""
        # shape-bucketed batches, capped so padding never exceeds the
        # memory-bounded batch size
        pad = max(0, min(_bucket(len(ids)), bs) - len(ids))
        return self._load_cached(em, profile, ids + ids[:1] * pad,
                                 headroom=len(query_tokens) + 2,
                                 n_real=len(ids))

    def _flush_fn(self, model_name: str):
        """(jitted decode fn, fused, donate) for a flush of this model."""
        fused = self.fused and supports_fused_decode(
            self.models[model_name].cfg)
        # donation needs exclusive ownership of the cache buffers: the
        # device-resident LRU would hand the same buffers to the next hit
        donate = self.async_h2d and not self.device_cache
        fn = self._decode_fn(model_name, fused,
                             KOPS.resolve_backend(self.kernels), donate)
        return fn, fused, donate

    def lower_flush(self, model_name: str, profile_ratio: float,
                    item_ids: Sequence[int], query_tokens: Sequence[int],
                    quant: bool = False) -> "jax.stages.Lowered":
        """The decode program one flush of `item_ids` would run, lowered
        but not executed: the same jitted function, kernels backend,
        params and padded cache shapes as `run_filter` / `run_map`.
        `.as_text()` shows which attention kernel the flush calls (a
        compiled Pallas kernel appears as `tpu_custom_call`)."""
        em = self.models[model_name]
        profile = Profile(model_name, profile_ratio, quant)
        ids = list(item_ids)
        bs = self._batch_size(profile, ids)
        pad = max(0, min(_bucket(len(ids)), bs) - len(ids))
        fn, _, _ = self._flush_fn(model_name)
        placement = self._placement()
        with self._device_ctx(placement):
            cache = self._load_for(em, profile, ids, query_tokens, bs)
            q = jnp.asarray([list(query_tokens)] * (len(ids) + pad),
                            jnp.int32)
            return fn.lower(self._params_for(em, model_name, placement),
                            cache, q)

    def _flush(self, em: EngineModel, profile: Profile, ids: List[int],
               query_tokens: Sequence[int], bs: int, preloaded=None):
        """One decode flush: load (or device-cache-hit, or take the
        prefetched) caches, run the query, return logits (len(ids) rows,
        NOT yet forced to host — callers np.asarray when they consume,
        which is what lets the next batch's H2D hide behind the decode).
        Runs in the span `stretto.engine.flush`, the load in
        `stretto.engine.load` and the jitted call in
        `stretto.engine.dispatch`."""
        pad = max(0, min(_bucket(len(ids)), bs) - len(ids))
        fn, fused, donate = self._flush_fn(profile.model_name)
        placement = self._placement()
        with obs.span("stretto.engine.flush", tuples=len(ids)), \
                self._device_ctx(placement):
            cache = preloaded if preloaded is not None else \
                self._load_for(em, profile, ids, query_tokens, bs)
            donated = sum(v.nbytes for v in cache.values()
                          if hasattr(v, "nbytes")) if donate else 0
            with obs.span("stretto.engine.dispatch"):
                params = self._params_for(em, profile.model_name, placement)
                q = jnp.asarray([list(query_tokens)] * (len(ids) + pad),
                                jnp.int32)
                logits, _ = fn(params, cache, q)
                logits = logits[:len(ids)]
        self._counters().donated_bytes += donated
        self.attn_dispatches += 1 if fused else len(query_tokens)
        return logits

    def _iter_flushes(self, em: EngineModel, profile: Profile,
                      item_ids: Sequence[int], query_tokens: Sequence[int],
                      bs: int):
        """Yield (start, ids, logits) per flush batch. With `async_h2d`
        and more than one batch, batch i+1's caches are loaded (npz read
        + pad + H2D copy) right after batch i's decode is *dispatched*
        and before its logits are forced, in the span
        `stretto.engine.prefetch`: the consumer's np.asarray then blocks
        on the decode while the transfer proceeds. The load's read,
        assemble, h2d and layout seconds are also counted into
        `prefetch_s`."""
        batches = [(s, list(item_ids[s:s + bs]))
                   for s in range(0, len(item_ids), bs)]
        prefetch = self.async_h2d and len(batches) > 1
        pre = None
        for bi, (s, ids) in enumerate(batches):
            logits = self._flush(em, profile, ids, query_tokens, bs,
                                 preloaded=pre)
            pre = None
            if prefetch and bi + 1 < len(batches):
                nxt = batches[bi + 1][1]
                c = self._counters()
                before = c.read_s + c.assemble_s + c.h2d_s + c.layout_s
                with obs.span("stretto.engine.prefetch"), \
                        self._device_ctx(self._placement()):
                    pre = self._load_for(em, profile, nxt, query_tokens, bs)
                c.prefetch_s += (c.read_s + c.assemble_s + c.h2d_s
                                 + c.layout_s - before)
            yield s, ids, logits
    def run_filter(self, model_name: str, profile_ratio: float,
                   item_ids: Sequence[int], query_tokens: Sequence[int],
                   yes_token: int, no_token: int,
                   quant: bool = False) -> np.ndarray:
        """Log-odds per item: logit(yes) - logit(no), prefill skipped."""
        em = self.models[model_name]
        profile = Profile(model_name, profile_ratio, quant)
        out = np.zeros(len(item_ids), np.float32)
        bs = self._batch_size(profile, item_ids)
        with self._counting_compiles():
            for s, ids, logits in self._iter_flushes(em, profile, item_ids,
                                                     query_tokens, bs):
                lo = logits[:, yes_token] - logits[:, no_token]
                with obs.span("stretto.engine.wait") as wait:
                    out[s:s + len(ids)] = np.asarray(lo, np.float32)
                self._counters().wait_s += wait.secs
        return out

    def run_map(self, model_name: str, profile_ratio: float,
                item_ids: Sequence[int], query_tokens: Sequence[int],
                value_tokens: Sequence[int], quant: bool = False
                ) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy value among `value_tokens` + confidence (logit margin)."""
        em = self.models[model_name]
        profile = Profile(model_name, profile_ratio, quant)
        vals = np.zeros(len(item_ids), np.int64)
        confs = np.zeros(len(item_ids), np.float32)
        bs = self._batch_size(profile, item_ids)
        with self._counting_compiles():
            vt = jnp.asarray(list(value_tokens))
            for s, ids, logits in self._iter_flushes(em, profile, item_ids,
                                                     query_tokens, bs):
                vlogits = logits[:, vt]                    # (B, n_vals)
                top2 = jax.lax.top_k(vlogits, 2)[0]
                best = vt[jnp.argmax(vlogits, -1)]
                margin = top2[:, 0] - top2[:, 1]
                with obs.span("stretto.engine.wait") as wait:
                    vals[s:s + len(ids)] = np.asarray(best)
                    confs[s:s + len(ids)] = np.asarray(margin)
                self._counters().wait_s += wait.secs
        return vals, confs


def _bucket(n: int) -> int:
    """Round batch size up to a power of two: bounded jit-shape diversity
    across cascade stages (dispatch overhead, not semantics). Callers cap
    the result at the memory-bounded batch size (see _flush)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _take_item(bi: int):
    def f(leaf):
        if leaf.ndim == 1:           # lengths
            return leaf[bi:bi + 1]
        return leaf[:, bi:bi + 1]    # (L, B, ...)
    return f


def _pad_tokens(token_lists: Sequence[Sequence[int]],
                multiple: int = 16) -> jnp.ndarray:
    n = max(len(t) for t in token_lists)
    n = (n + multiple - 1) // multiple * multiple
    out = np.zeros((len(token_lists), n), np.int32)
    for i, t in enumerate(token_lists):
        out[i, :len(t)] = t
    return jnp.asarray(out)
