"""The readers of the program's flush counters, and the counters against
the benchmark's own span.

`assemble_share`, `h2d_share`, `flush_wait_share` and `flush_compiles`
read only the window's StageStats and its length: they are checked on
windows built by hand, and give no reading for a program whose StageStats
lack the counters. A test-size run on the CPU, with a device LRU cut so
that the window's flushes load their batches, checks that the program's
padded bytes over the window equal what the benchmark's span around
`CacheStore.load_batch` saw.
"""
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from sbench import harness  # noqa: E402

from repro.obs import FlushCounters  # noqa: E402
from repro.runtime.executor import StageStats  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
READERS = ("assemble_share", "h2d_share", "flush_wait_share",
           "flush_compiles")


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "metric_" + name).read


def _stats(**flush):
    return StageStats("op", 0, 0, flush=FlushCounters(**flush))


def test_readers_on_a_window_by_hand():
    w = harness.Window(seconds=20.0, stage_stats=[
        _stats(read_s=0.5, assemble_s=3.5, h2d_s=2.0, wait_s=1.0,
               compiles=2),
        _stats(read_s=0.0, assemble_s=1.0, h2d_s=0.5, wait_s=0.25,
               compiles=1)])
    assert _reader("assemble_share")(w) == pytest.approx(25.0)
    assert _reader("h2d_share")(w) == pytest.approx(12.5)
    assert _reader("flush_wait_share")(w) == pytest.approx(6.25)
    assert _reader("flush_compiles")(w) == 3


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_counters(name):
    """A window with no flushes, or StageStats from a program that keeps
    no flush counters, gives no reading and raises nothing."""
    assert _reader(name)(harness.Window(seconds=20.0)) is None
    bare = SimpleNamespace(n_tuples=8, n_batches=1, kv_bytes=0)
    assert _reader(name)(harness.Window(seconds=20.0,
                                        stage_stats=[bare])) is None


def _cold_checkout(tmp_path):
    """The test-size scan cell with its device LRU cut as the granite cell's
    is, to `max_batch` of the longest document's ratio-0 caches (2 layers,
    96 tokens, 2 KV heads of 16, bf16 K and V), so flushes stay full and
    the window's flushes load their batches."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, "tiny_scan.json"),
                root / "bench" / "traffic" / "tiny_scan.json")
    cfg = json.load(open(os.path.join(DATA, "tiny.json")))
    cfg["deployment"]["memory_budget_bytes"] = 8 * 2 * 96 * 2 * 16 * 2 * 2
    (root / "bench" / "configs" / "tiny-cold.json").write_text(
        json.dumps(cfg))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-cold", "source": "test size",
                             "file": "bench/configs/tiny-cold.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny.scan_cold",
                               "config": "tiny-cold",
                               "traffic": "tiny_scan", "chips": 1,
                               "why": "test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.find_cell("tiny.scan_cold", root=str(root),
                             bench_dir=str(root / "bench"))


def test_padded_bytes_match_the_load_span(tmp_path, monkeypatch):
    windows = []

    class Kept(harness.Window):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            windows.append(self)

    monkeypatch.setattr(harness, "Window", Kept)
    cell = _cold_checkout(tmp_path)
    out = harness.run_cell(cell, 3000000003, 0.5, False, time.perf_counter(),
                           require_chip=False, compile_cache=False,
                           log=lambda *a: None)
    w, = windows
    assert out["correct"], out["checks"]
    assert out["info"]["loads"] > 0 and w.cache_misses > 0
    padded = sum(s.flush.padded_bytes for s in w.stage_stats)
    assert padded == out["info"]["padded_bytes"] == w.load.padded
    for name in READERS[:3]:
        assert _reader(name)(w) > 0, name
    assert _reader("flush_compiles")(w) == out["info"]["window_compiles"]
