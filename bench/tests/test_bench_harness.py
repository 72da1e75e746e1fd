"""The benchmark harness on the CPU, at a test size.

A run of bench/run.py without a TPU must refuse and print no result. A
cell, a traffic mix and a per-layer metric added as new files, with
entries in BENCHMARK.json, must be found by name. And the check that
decides `correct` must pass the program at a test size, and fail the
float8 control and each fault a scan cell can have: an answer altered
where it is produced, and half of each flush left out.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from sbench import harness, traffic  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


def _checkout(tmp_path):
    """A checkout with the test-size cell `tiny.scan` added by files and
    BENCHMARK.json entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, "tiny_scan.json"),
                root / "bench" / "traffic" / "tiny_scan.json")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny", "source": "test size",
                             "file": "bench/tests/data/tiny.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny.scan", "config": "tiny",
                               "traffic": "tiny_scan", "chips": 1,
                               "why": "test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), str(root / "bench")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root, bench_dir = _checkout(tmp_path_factory.mktemp("bench"))
    return harness.find_cell("tiny.scan", root=root, bench_dir=bench_dir)


def _run(cell, seed, **kw):
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            require_chip=False, compile_cache=False,
                            log=lambda *a: None, **kw)


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "granite8b.scan_cold", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_new_files_are_found_by_name(tmp_path):
    root, bench_dir = _checkout(tmp_path)
    before = {}
    for d, _, files in os.walk(bench_dir):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    cfg = json.load(open(os.path.join(DATA, "tiny.json")))
    cfg["num_hidden_layers"] = 3
    with open(os.path.join(bench_dir, "configs", "new-model.json"), "w") as f:
        json.dump(cfg, f)
    mix = json.load(open(os.path.join(DATA, "tiny_scan.json")))
    mix["n_docs"] = 7
    with open(os.path.join(bench_dir, "traffic", "new_mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench_dir, "metrics", "new_metric.x.py"),
              "w") as f:
        f.write("def read(w):\n    return 42.0\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "new-model", "source": "test",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new.cell", "config": "new-model",
                               "traffic": "new_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric.x", "unit": "x",
                               "better": "higher", "source": "host_clock",
                               "layer": "runtime", "moves": "tuples_per_s",
                               "workloads": ["new.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = harness.find_cell("new.cell", root=root, bench_dir=bench_dir)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["n_docs"] == 7
    assert len(traffic.make_corpus(cell.traffic, 5, 512)) == 7
    assert "new_metric.x" in [m["name"] for m in cell.per_layer]
    assert cell.reader("new_metric.x")(None) == 42.0
    assert cell.reference().Reference(cell.config).m["L"] == 3
    # the new cell is not reported by the metric's other cells
    other = harness.find_cell("tiny.scan", root=root, bench_dir=bench_dir)
    assert "new_metric.x" not in [m["name"] for m in other.per_layer]
    for path, data in before.items():
        assert open(path, "rb").read() == data, path


def test_program_passes_and_float8_control_fails(tiny):
    out = _run(tiny, 3000000001)
    lim = tiny.config["limits"]
    assert out["correct"], out["checks"]
    assert out["checks"]["score_gap"]["value"] <= lim["score_gap"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tuples_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["info"]["window_compiles"] == 0
    # the control in the program's place: the run itself reads false,
    # on the same seed, while the program's own numbers stay in limits
    ctl = _run(tiny, 3000000001, control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["score_gap"]["value"] > lim["score_gap"]
    assert ctl["info"]["program"] == {
        k: v["value"] for k, v in out["checks"].items()}


@pytest.mark.parametrize("fault", sorted(harness.FAULTS))
def test_fault_in_timed_path_fails_check(tiny, fault):
    out = _run(tiny, 3000000002, fault=fault)
    assert not out["correct"], out["checks"]
