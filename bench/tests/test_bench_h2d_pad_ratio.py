"""The `h2d_pad_ratio` reader on windows built by hand: the bytes that
crossed to the device over the real bytes of the loaded tuples, and no
reading where the window loaded nothing or the program keeps no such
counter."""
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from sbench import harness  # noqa: E402

from repro.obs import FlushCounters  # noqa: E402
from repro.runtime.executor import StageStats  # noqa: E402

read = harness.load_module(
    os.path.join(BENCH, "metrics", "h2d_pad_ratio.py"),
    "metric_h2d_pad_ratio").read


def _stats(**flush):
    return StageStats("op", 0, 0, flush=FlushCounters(**flush))


def _window(stage_stats, real):
    load = SimpleNamespace(real=real, padded=2 * real, calls=len(stage_stats))
    return harness.Window(seconds=20.0, stage_stats=stage_stats, load=load)


def test_bytes_sent_over_real_bytes():
    w = _window([_stats(h2d_bytes=600, padded_bytes=1200),
                 _stats(h2d_bytes=480, padded_bytes=900),
                 _stats(lru_hit_bytes=4000)],      # a stage served from LRU
                real=1000)
    assert read(w) == pytest.approx(1.08)


@pytest.mark.parametrize("stage_stats,real", [
    ([], 0),                                     # no flush in the window
    ([_stats(), _stats()], 0),                   # no batch loaded
    ([SimpleNamespace(n_tuples=8, n_batches=1)], 1000),  # no flush counters
    ([SimpleNamespace(flush=SimpleNamespace(     # counters without h2d_bytes
        read_s=0.1, assemble_s=2.0, padded_bytes=2000))], 1000),
], ids=["empty", "nothing-loaded", "no-flush-counters", "no-h2d-counter"])
def test_no_reading_without_loads_or_counter(stage_stats, real):
    assert read(_window(stage_stats, real)) is None


def test_no_reading_without_the_load_span():
    w = harness.Window(seconds=20.0, stage_stats=[_stats(h2d_bytes=600)])
    assert read(w) is None
