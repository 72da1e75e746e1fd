"""The `staging_reuse_share` reader on windows built by hand: the share of
batch loads that reused the engine's staging buffers, and no reading
where the program keeps no such counters or the window loaded nothing."""
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
    os.path.join(BENCH, "metrics", "staging_reuse_share.py"),
    "metric_staging_reuse_share").read


def _stats(**flush):
    return StageStats("op", 0, 0, flush=FlushCounters(**flush))


def test_share_of_loads_that_reused_a_buffer():
    w = harness.Window(seconds=20.0, stage_stats=[
        _stats(staged_reuses=5, staged_allocs=1),
        _stats(staged_reuses=0, staged_allocs=1),
        _stats(staged_reuses=2, staged_allocs=1),
        _stats()])                               # a stage that hit the LRU
    assert read(w) == pytest.approx(70.0)
    w.stage_stats = [_stats(staged_reuses=4)]
    assert read(w) == 100.0


@pytest.mark.parametrize("stage_stats", [
    [],                                          # no flush in the window
    [_stats(), _stats()],                        # no batch loaded
    [SimpleNamespace(n_tuples=8, n_batches=1)],  # StageStats with no flush
    [SimpleNamespace(flush=SimpleNamespace(      # counters without staging
        read_s=0.1, assemble_s=2.0, padded_bytes=64))],
], ids=["empty", "nothing-loaded", "no-flush-counters", "no-staging-counters"])
def test_no_reading_without_loads_or_counters(stage_stats):
    assert read(harness.Window(seconds=20.0, stage_stats=stage_stats)) is None
