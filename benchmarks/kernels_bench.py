"""Kernel microbenchmarks + the roofline-anchored CI perf gate.

Two modes:

  run()     — rows consumed by benchmarks/run.py's CSV (name, us_per_call,
              derived): wall time per variant plus Pallas interpret-mode
              correctness spot checks.

  --gate    — the CI perf gate: times the serving-path attention variants
              (single-query decode, fused multi-token query, int8 KV) on
              both the ref and Pallas(interpret) backends, records
              wall-time-per-tuple against the analytic roofline bound
              (benchmarks/roofline.py), writes a BENCH_<ts>-<sha>.json
              trajectory artifact, and fails (exit != 0) on
                * Pallas lowering/correctness errors (interpret mode), or
                * a >25% wall-time-per-tuple regression on any variant vs
                  the newest previous BENCH_*.json artifact.

Timing blocks every rep (async dispatch would otherwise under-time all
but the last) and takes the min over reps — the least-noise estimator for
a CI runner.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

from repro.kernels import ops, ref  # noqa: E402
from repro.launch.mesh import peaks_for  # noqa: E402
import roofline  # noqa: E402

# gate shapes: small enough for interpret mode on a CPU runner, big
# enough that per-call wall time dominates dispatch overhead
GATE_B, GATE_S, GATE_KV, GATE_G, GATE_DK = 4, 256, 2, 2, 64
GATE_LQ = 6


def _time(fn, *args, reps: int = 5) -> float:
    """Min wall time per call over `reps`, blocking EVERY rep (async
    dispatch under-times all but the last otherwise)."""
    fn(*args).block_until_ready()          # warmup / compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def _gate_inputs(key, quant: bool = False):
    ks = jax.random.split(key, 3)
    B, S, KV, G, dk = GATE_B, GATE_S, GATE_KV, GATE_G, GATE_DK
    q1 = jax.random.normal(ks[0], (B, KV, G, dk), jnp.float32)
    qm = jnp.broadcast_to(q1[:, None], (B, GATE_LQ, KV, G, dk))
    kc = jax.random.normal(ks[1], (B, S, KV, dk), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, KV, dk), jnp.float32)
    lens = jnp.asarray([S, S - 40, S // 2, 37], jnp.int32)
    if not quant:
        return q1, qm, kc, vc, lens, None, None
    k_s = jnp.max(jnp.abs(kc), -1) / 127.0
    v_s = jnp.max(jnp.abs(vc), -1) / 127.0
    k_q = jnp.round(kc / k_s[..., None]).astype(jnp.int8)
    v_q = jnp.round(vc / v_s[..., None]).astype(jnp.int8)
    return q1, qm, k_q, v_q, lens, k_s, v_s


def _variant_rows(backend: str, reps: int = 5) -> List[Dict]:
    """wall-time-per-tuple + roofline bound for the three serving-path
    attention variants under one kernels backend."""
    key = jax.random.PRNGKey(0)
    B, S, KV, G, dk = GATE_B, GATE_S, GATE_KV, GATE_G, GATE_DK
    q1, qm, kc, vc, lens, _, _ = _gate_inputs(key)
    _, _, k_q, v_q, _, k_s, v_s = _gate_inputs(key, quant=True)
    rows = []

    def row(name, dt, n_q, kv_bytes_per_elem, scale_bytes):
        bound = roofline.decode_bound_s(
            B, S, KV, G, dk, dk, n_q=n_q,
            kv_bytes_per_elem=kv_bytes_per_elem, scale_bytes=scale_bytes)
        per_tuple = dt / B
        rows.append({
            "name": f"{name}_{backend}",
            "us_per_call": dt * 1e6,
            "wall_us_per_tuple": per_tuple * 1e6,
            "roofline_us_per_tuple": bound["bound_s"] / B * 1e6,
            "derived": (f"per_tuple={per_tuple * 1e6:.1f}us;"
                        f"bound={bound['bound_s'] / B * 1e6:.1f}us;"
                        f"dom={bound['dominant']}"),
        })

    f = jax.jit(lambda *a: ops.decode_attention(*a, backend=backend))
    row("decode", _time(f, q1, kc, vc, lens, reps=reps), 1, 4, 0)

    f = jax.jit(lambda *a: ops.decode_query_attention(*a, backend=backend))
    row("fused_query", _time(f, qm, kc, vc, lens, reps=reps), GATE_LQ, 4, 0)

    f = jax.jit(lambda q, k, v, l, ks_, vs_: ops.decode_attention(
        q, k, v, l, backend=backend, k_scale=ks_, v_scale=vs_))
    row("decode_int8", _time(f, q1, k_q, v_q, lens, k_s, v_s, reps=reps),
        1, 1, 4)
    return rows


def _lowering_checks() -> List[Dict]:
    """Pallas interpret-mode vs ref parity on the gate shapes. Any
    lowering error raises; any mismatch reports err > tol for the gate
    to fail on."""
    key = jax.random.PRNGKey(1)
    q1, qm, kc, vc, lens, _, _ = _gate_inputs(key)
    _, _, k_q, v_q, _, k_s, v_s = _gate_inputs(key, quant=True)
    checks = []

    d = ops.decode_attention(q1, kc, vc, lens, backend="interpret")
    r = ref.decode_attention_ref(q1, kc, vc, lens)
    checks.append(("decode", float(jnp.max(jnp.abs(d - r))), 1e-4))

    d = ops.decode_query_attention(qm, kc, vc, lens, backend="interpret")
    r = ref.decode_query_attention_ref(qm, kc, vc, lens)
    checks.append(("fused_query", float(jnp.max(jnp.abs(d - r))), 1e-4))

    d = ops.decode_attention(q1, k_q, v_q, lens, backend="interpret",
                             k_scale=k_s, v_scale=v_s)
    r = ref.decode_attention_ref(q1, k_q.astype(jnp.float32) * k_s[..., None],
                                 v_q.astype(jnp.float32) * v_s[..., None],
                                 lens)
    checks.append(("decode_int8", float(jnp.max(jnp.abs(d - r))), 1e-4))

    return [{"name": f"lowering_{n}", "us_per_call": 0.0, "err": e,
             "tol": t, "ok": e <= t, "derived": f"maxerr={e:.2e}"}
            for n, e, t in checks]


def run() -> List[Dict]:
    """Rows for benchmarks/run.py: ref-backend wall times for every
    serving-path variant, plus the interpret-mode parity spot checks."""
    return _variant_rows("ref") + _lowering_checks()


# ---------------------------------------------------------------------------
# CI perf gate
# ---------------------------------------------------------------------------

def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "nogit"
    except Exception:
        return "nogit"


def _latest_artifact(dirpath: str, exclude: Optional[str] = None
                     ) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(dirpath, "BENCH_*.json")))
    paths = [p for p in paths if os.path.abspath(p) != exclude]
    return paths[-1] if paths else None


def gate(out_dir: str, baseline_dir: Optional[str] = None,
         max_regression: float = 0.25, reps: int = 5) -> int:
    """Run the perf gate; returns the process exit code."""
    os.makedirs(out_dir, exist_ok=True)
    checks = _lowering_checks()
    rows = _variant_rows("ref", reps=reps) \
        + _variant_rows("interpret", reps=reps)

    peaks = peaks_for()
    artifact = {
        "schema": "stretto-kernels-bench-v1",
        "ts": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime()),
        "sha": _git_sha(),
        "backend_device": jax.default_backend(),
        "peaks": peaks.name,
        "shapes": {"B": GATE_B, "S": GATE_S, "KV": GATE_KV, "G": GATE_G,
                   "dk": GATE_DK, "Lq": GATE_LQ},
        "lowering": checks,
        "rows": rows,
    }

    failed = False
    for c in checks:
        status = "ok" if c["ok"] else "FAIL"
        print(f"[lowering] {c['name']}: {c['derived']} ({status})")
        failed |= not c["ok"]

    print(f"[gate] roofline priced against peak set "
          f"{peaks.name!r} "
          f"({peaks.flops / 1e9:.0f} GFLOP/s, "
          f"{peaks.hbm_bw / 1e9:.0f} GB/s)")
    for r in rows:
        print(f"[perf] {r['name']}: {r['wall_us_per_tuple']:.1f} us/tuple "
              f"(roofline bound {r['roofline_us_per_tuple']:.1f})")

    baseline_dir = baseline_dir or out_dir
    prev_path = _latest_artifact(baseline_dir)
    if prev_path:
        with open(prev_path) as f:
            prev = {r["name"]: r for r in json.load(f).get("rows", [])}
        for r in rows:
            old = prev.get(r["name"])
            if not old or "wall_us_per_tuple" not in old:
                continue
            ratio = r["wall_us_per_tuple"] / max(old["wall_us_per_tuple"],
                                                 1e-9)
            delta_us = r["wall_us_per_tuple"] - old["wall_us_per_tuple"]
            # the absolute floor keeps sub-50us dispatch jitter from
            # tripping the relative threshold on fast variants
            if ratio > 1.0 + max_regression and delta_us > 50.0:
                print(f"[gate] REGRESSION {r['name']}: "
                      f"{old['wall_us_per_tuple']:.1f} -> "
                      f"{r['wall_us_per_tuple']:.1f} us/tuple "
                      f"({(ratio - 1) * 100:.0f}% > "
                      f"{max_regression * 100:.0f}%) vs {prev_path}")
                failed = True
        print(f"[gate] compared against {prev_path}")
    else:
        print("[gate] no previous BENCH_*.json artifact; recording baseline")

    out_path = os.path.join(
        out_dir, f"BENCH_{artifact['ts']}-{artifact['sha']}.json")
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"[gate] wrote {out_path}")
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gate", action="store_true",
                    help="run the CI perf gate (exit != 0 on lowering "
                         "errors or wall-time regressions)")
    ap.add_argument("--out", default="results/bench",
                    help="directory for the BENCH_*.json artifact")
    ap.add_argument("--baseline", default=None,
                    help="directory holding the previous BENCH_*.json "
                         "(default: --out)")
    ap.add_argument("--max-regression", type=float, default=0.25,
                    help="max tolerated wall-time-per-tuple regression")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)
    if args.gate:
        return gate(args.out, args.baseline, args.max_regression, args.reps)
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
