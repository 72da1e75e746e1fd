"""Spans and per-flush counters: the program's own tracing.

Spans (`span`) are `jax.profiler.TraceAnnotation`s named
`stretto.<layer>.<what>`. They land in the profiler's trace, on the clock
of the device ops, and the profiler keeps them in memory until its
session stops. With no session running a span costs the annotation call
and two clock reads, so spans are always on. Each span times itself on
the host clock (`secs`): the counters below are those times. A span hands
its `exec` and `op` arguments on to the spans opened inside it on the
same thread, so every span of one execution and operator carries the
same identifiers.

`FlushCounters` is the per-flush counter set. The engine keeps one per
thread (`ServingEngine.transfer_stats_local`), the runtime takes its
delta around each flush (`run_operator`) and sums the deltas into
`StageStats.flush`.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, fields
from typing import Any, Dict, Tuple

import jax

# span arguments that nested spans on the same thread inherit
INHERITED = ("exec", "op")
# JAX's monitoring event around building (or loading from the persistent
# cache) one XLA executable
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tl = threading.local()
_exec_ids = itertools.count(1)
_listen_lock = threading.Lock()
_listening = False


def new_exec_id() -> int:
    """A process-unique id for one execution or plan: the `exec` argument
    its spans share."""
    return next(_exec_ids)


class span:
    """``with span("stretto.engine.load", op=name) as sp:`` opens a trace
    annotation over the block and times it; `sp.secs` holds the block's
    host seconds once it exits."""

    __slots__ = ("name", "args", "secs", "_ann", "_t0", "_outer")

    def __init__(self, name: str, **args: Any):
        self.name, self.args, self.secs = name, args, 0.0

    def __enter__(self) -> "span":
        outer = getattr(_tl, "ctx", None) or {}
        args = {**outer, **self.args}
        self._outer = outer
        _tl.ctx = {k: args[k] for k in INHERITED if k in args}
        self._ann = jax.profiler.TraceAnnotation(self.name, **args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.secs = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _tl.ctx = self._outer


def _on_duration(event: str, secs: float, **_: Any) -> None:
    if event == COMPILE_EVENT:
        _tl.compiles = getattr(_tl, "compiles", 0) + 1
        _tl.compile_s = getattr(_tl, "compile_s", 0.0) + secs


def compiles_here() -> Tuple[int, float]:
    """XLA compiles made so far on the calling thread (persistent-cache
    loads included) and their seconds. The listener is registered on the
    first call, so callers take deltas, never totals."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
                _listening = True
    return getattr(_tl, "compiles", 0), getattr(_tl, "compile_s", 0.0)


@dataclass
class FlushCounters:
    """Where a flush's host time went, and what it moved. Times are host
    seconds of the matching `stretto.*` spans; counts are exact."""
    read_s: float = 0.0        # shard reads (stretto.store.read)
    assemble_s: float = 0.0    # copying each item into place in the
    #                            reused staging buffers
    #                            (stretto.store.assemble)
    h2d_s: float = 0.0         # jnp.asarray copies of the batch to the
    #                            device, and waiting for the last copy out
    #                            of a staging buffer to land before it is
    #                            refilled (stretto.store.h2d)
    layout_s: float = 0.0      # dispatching the device program that pads
    #                            a sequence key's flat buffer into the
    #                            batch, its compile on a new flat length
    #                            included, which compile_s counts too
    #                            (stretto.store.layout)
    wait_s: float = 0.0        # blocked forcing a flush's result to the
    #                            host (stretto.engine.wait)
    prefetch_s: float = 0.0    # the part of read_s + assemble_s + h2d_s
    #                            + layout_s spent on batch i+1 before batch
    #                            i's result was forced: an upper bound on
    #                            the time the device hid; only a device
    #                            trace shows the time actually hidden
    compile_s: float = 0.0     # XLA compiles on the flush's thread
    compiles: int = 0
    padded_bytes: int = 0      # bytes of the padded batches built
    h2d_bytes: int = 0         # bytes sent to the device to build them:
    #                            sequence keys unpadded, back to back
    donated_bytes: int = 0     # consumed cache buffers donated to XLA
    staged_reuses: int = 0     # batch loads the staging buffers had room for
    staged_allocs: int = 0     # batch loads that allocated or grew one
    lru_hit_bytes: int = 0     # bytes of the batches served from the
    #                            device LRU (stretto.engine.lookup hit=1):
    #                            the hot counterpart of padded_bytes
    lru_evictions: int = 0     # device-LRU entries this thread's loads
    #                            pushed out to stay within the budget

    def __add__(self, other: "FlushCounters") -> "FlushCounters":
        return FlushCounters(**{f.name: getattr(self, f.name)
                                + getattr(other, f.name)
                                for f in fields(self)})

    def __sub__(self, other: "FlushCounters") -> "FlushCounters":
        return FlushCounters(**{f.name: getattr(self, f.name)
                                - getattr(other, f.name)
                                for f in fields(self)})

    def as_dict(self) -> Dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FlushCounters":
        """The set from `as_dict` output (the remote protocol's form);
        names this version does not know are ignored."""
        return cls(**{f.name: d[f.name] for f in fields(cls)
                      if f.name in d})
