"""Dry-run smoke: one production-mesh cell compiled in a subprocess (the
512-device XLA flag must be set before jax init, so this cannot run
in-process with the rest of the suite)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.mark.parametrize("arch,shape", [("granite-8b", "decode_32k")])
def test_dryrun_cell_compiles(arch, shape):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", arch, "--shape", shape],
        capture_output=True, text=True, timeout=480, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout)
    assert rec["ok"]
    assert rec["n_devices"] == 256
    assert rec["roofline"]["dominant"] in ("compute", "memory",
                                           "collective")
    assert rec["hlo_flops_per_dev"] > 0


def test_dryrun_failing_cell_exits_nonzero():
    """A cell that fails is recorded as ok=false AND fails the process,
    so a script or CI job running the dry run cannot miss it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "no-such-arch", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert out.returncode != 0
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] is False and rec["arch"] == "no-such-arch"
