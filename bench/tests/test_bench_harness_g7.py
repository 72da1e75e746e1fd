"""The check that decides `correct`, at 7 query heads per KV head.

Yi-34B groups 7 query heads on each KV head, so the query rows per KV
head are not a power of two. At the test size `tiny_g7` (14 query heads
over 2 KV heads) the program must agree with the `gqa_dense` reference
on seeded weights, and the float8 control must fail the same check.
"""
import json
import os
import shutil
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from sbench import harness  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


@pytest.fixture(scope="module")
def tiny_g7(tmp_path_factory):
    """The test-size cell `tiny_g7.scan`, added by files and
    BENCHMARK.json entries only."""
    root = tmp_path_factory.mktemp("bench") / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, "tiny_scan.json"),
                root / "bench" / "traffic" / "tiny_scan.json")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny_g7", "source": "test size",
                             "file": "bench/tests/data/tiny_g7.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny_g7.scan", "config": "tiny_g7",
                               "traffic": "tiny_scan", "chips": 1,
                               "why": "test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.find_cell("tiny_g7.scan", root=str(root),
                             bench_dir=str(root / "bench"))


def _run(cell, seed, **kw):
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            require_chip=False, compile_cache=False,
                            log=lambda *a: None, **kw)


def test_program_passes_and_float8_control_fails_at_g7(tiny_g7):
    cfg = tiny_g7.config
    assert cfg["num_attention_heads"] // cfg["num_key_value_heads"] == 7
    out = _run(tiny_g7, 3000000001)
    lim = cfg["limits"]
    assert out["correct"], out["checks"]
    assert out["checks"]["score_gap"]["value"] <= lim["score_gap"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tuples_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["info"]["window_compiles"] == 0
    # the control in the program's place: the run itself reads false,
    # on the same seed, while the program's own numbers stay in limits
    ctl = _run(tiny_g7, 3000000001, control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["score_gap"]["value"] > lim["score_gap"]
    assert ctl["info"]["program"] == {
        k: v["value"] for k, v in out["checks"].items()}
