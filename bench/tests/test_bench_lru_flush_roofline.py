"""The readers of the hot cell's per-layer metrics: `lru_evictions` (the
program's device-LRU counter) and `flush_roofline` (the flushes' layer
loops against their least time).

Both are checked on windows built by hand, and give no reading where
there is nothing to read: a program without the counter, a window with no
device trace. `flush_roofline` tells the flush programs' layer loops from
other device work by the trace's `%while` ops. On the short trace recorded
on a TPU v5e (data/trace_small.json.gz, a traced run of
`granite8b.scan_cold`, 9 layers) each of them holds one flush's nine
decode-kernel calls, and the engine's small programs on the logits lie
outside them; so on a short trace of a traced run of `yi34b.scan_hot`
(data/trace_yi_small.json.gz: 10 flushes, 6 layers, the decode kernel at
7 query rows per KV head; the window span cut to them).
"""
import gzip
import json
import os
import re
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from sbench import harness  # noqa: E402
from sbench import trace as TRC  # noqa: E402
from sbench.peaks import peaks_for  # noqa: E402

from repro.obs import FlushCounters  # noqa: E402
from repro.runtime.executor import StageStats  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
YI = harness.load_json(os.path.join(BENCH, "configs", "yi-34b.json"))
GRANITE = harness.load_json(os.path.join(BENCH, "configs",
                                         "granite-8b.json"))


def _module(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "metric_" + name)


LRU = _module("lru_evictions")
ROOF = _module("flush_roofline")
DECODE = _module("decode_query_attention_roofline")


def _stats(**flush):
    return StageStats("op", 0, 0, flush=FlushCounters(**flush))


# ---------------------------------------------------------------- lru

def test_lru_evictions_sums_the_window():
    w = harness.Window(stage_stats=[_stats(lru_evictions=2),
                                    _stats(lru_evictions=0),
                                    _stats(lru_evictions=5)])
    assert LRU.read(w) == 7
    w.stage_stats = [_stats(lru_hit_bytes=10 ** 9)]
    assert LRU.read(w) == 0                     # hot: nothing evicted


def test_lru_evictions_gives_nothing_without_the_counter():
    """A window with no flushes, or StageStats of a program whose flush
    counters lack `lru_evictions`, gives no reading and raises nothing."""
    assert LRU.read(harness.Window()) is None
    old = SimpleNamespace(flush=SimpleNamespace(read_s=0.1, wait_s=0.2))
    assert LRU.read(harness.Window(stage_stats=[old])) is None
    assert LRU.read(harness.Window(
        stage_stats=[SimpleNamespace(n_tuples=4)])) is None


# ---------------------------------------------------------------- roofline

def test_layer_counts_match_hand_counts_at_yi_widths():
    # per layer: q and o 7168x7168, k and v 7168x1024, three 7168x20480
    # MLP matrices, two norms of 7168; bf16; 6 layers
    per_layer = (2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 20480
                 + 2 * 7168)
    assert ROOF.layer_weight_bytes(YI) == 2 * 6 * per_layer
    # with the embedding and the untied head: the stage's 8.53 GB
    assert ROOF.layer_weight_bytes(YI) + 2 * 2 * 7168 * 64000 \
        == pytest.approx(8.53e9, rel=1e-3)
    assert ROOF.layer_matmul_flops(YI) == 2 * 6 * (
        2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 20480)


def _window(while_s, **kw):
    """64 tuples of 128 real positions at ratio 0, in one flush."""
    kv = 128 * 6 * 2 * 8 * 128 * 2
    scored = {("op", i): 1 for i in range(64)}
    args = dict(
        config=YI, peaks=peaks_for("TPU v5 lite"), scored=scored,
        work={k: (0.0, False, kv) for k in scored},
        cache_hits=1, cache_misses=0,
        trace={"op_time": {"%while.3 while": while_s,
                           "%fusion.58 fusion": 0.004,   # the head: outside
                           "%decode_query_attention.7 custom-call": 0.002,
                           "%sub.1 subtract": 1e-6}})
    return harness.Window(**{**args, **kw})


def test_flush_roofline_on_a_window_by_hand():
    w = _window(0.01)
    # bytes: the layers' weights once, then per tuple its 128 positions of
    # bf16 K and V (6 layers x 8 heads x 128) and the float32 query and
    # output rows (56 heads x 128, 6 layers)
    weights = 2 * 6 * (2 * 7168 * 7168 + 2 * 7168 * 1024
                       + 3 * 7168 * 20480 + 2 * 7168)
    nbytes = weights + 64 * (128 * 6 * 2 * 8 * 128 * 2
                             + 2 * 56 * 128 * 4 * 6)
    flops = 64 * (2 * 6 * (2 * 7168 * 7168 + 2 * 7168 * 1024
                           + 3 * 7168 * 20480)
                  + 2 * 2 * 56 * 128 * 129 * 6)
    assert nbytes / 819e9 > flops / 197e12        # memory bound
    assert ROOF.read(w) == pytest.approx(100 * nbytes / 819e9 / 0.01)
    # two flushes of the same tuples stream the weights twice
    w2 = _window(0.02, cache_hits=1, cache_misses=1)
    assert ROOF.read(w2) == pytest.approx(
        100 * (nbytes + weights) / 819e9 / 0.02)


def test_flush_roofline_gives_nothing_without_a_trace():
    assert ROOF.read(_window(0.01, trace=None)) is None
    assert ROOF.read(_window(0.01, peaks=None)) is None
    w = _window(0.01)
    w.trace = {"op_time": {"%fusion.58 fusion": 0.004}}   # no layer loop
    assert ROOF.read(w) is None
    w = _window(0.01)
    w.cache_hits = 0                                     # no flush
    assert ROOF.read(w) is None


@pytest.mark.parametrize("recording,config,layers", [
    ("trace_small.json.gz", GRANITE, 9),
    ("trace_yi_small.json.gz", YI, 6)], ids=["granite-8b", "yi-34b"])
def test_while_ops_are_the_flush_loops_in_the_recorded_trace(
        recording, config, layers):
    with gzip.open(os.path.join(DATA, recording), "rt") as f:
        events = json.load(f)
    dev = [e for e in events if e["plane"].startswith("/device:")]
    loops = [e for e in dev if "while" in e["name"]]
    assert loops and all(ROOF.WHILE_OP.fullmatch(e["name"]) for e in loops)
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in loops]

    def inside(e):
        return any(s <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= t
                   for s, t in spans)

    pattern = DECODE.kernel_pattern(config)
    kernels = [e for e in dev if re.match(pattern, e.get("call", ""))]
    # each loop is one flush: one decode-kernel call per layer
    assert len(kernels) == layers * len(loops)
    assert all(inside(e) for e in kernels)
    # the engine's small programs on the logits run between the loops
    small = [e for e in dev
             if e["name"] in ("%sub.1 subtract", "%select_n.1 select")]
    assert small and not any(inside(e) for e in small)
    # the reduced trace keeps the loops' time under their op names
    window = TRC.window_of(events)
    r = TRC.reduce_trace(events, window)
    clipped = sum(min(t, window[1]) - max(s, window[0])
                  for s, t in spans if t > window[0] and s < window[1])
    assert ROOF.loop_time(r) == pytest.approx(clipped * 1e-9)
    assert 0 < ROOF.loop_time(r) < r["busy_s"]
