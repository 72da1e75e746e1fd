"""Roofline table from the dry-run artifacts (results/dryrun_*), plus
analytic decode-attention bounds for the kernels perf gate."""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.mesh import peaks_for  # noqa: E402


def decode_bound_s(B: int, S: int, KV: int, G: int, dk: int, dv: int,
                   n_q: int = 1, kv_bytes_per_elem: int = 4,
                   scale_bytes: int = 0) -> Dict[str, float]:
    """Analytic roofline bound (seconds per call) for (fused) flash-decode
    over a cached context.

    The kernel streams the whole K/V cache once per call regardless of
    how many query tokens ride along — that is exactly why the fused
    multi-token path wins over n_q sequential dispatches, and why int8
    (kv_bytes_per_elem=1 + per-token scale_bytes) halves-plus the memory
    time. FLOPs scale with n_q; bytes for q/out are negligible next to
    the cache stream but included. Priced against the peaks of the
    device the process runs on (`launch.mesh.peaks_for`, which raises
    for a device it does not list).
    """
    peaks = peaks_for()
    kv_bytes = B * S * KV * (dk + dv) * kv_bytes_per_elem
    kv_bytes += B * S * KV * 2 * scale_bytes          # k_scale + v_scale
    qo_bytes = B * n_q * KV * G * (dk + dv) * 4
    flops = 2.0 * B * n_q * KV * G * S * (dk + dv)
    mem_s = (kv_bytes + qo_bytes) / peaks.hbm_bw
    compute_s = flops / peaks.flops
    return {"mem_s": mem_s, "compute_s": compute_s,
            "bound_s": max(mem_s, compute_s),
            "dominant": "memory" if mem_s >= compute_s else "compute",
            "peaks": peaks.name}


def load(out_dir: str = "results/dryrun_sp") -> List[Dict]:
    recs = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def table(recs: List[Dict]) -> List[str]:
    out = [f"{'arch':24s} {'shape':12s} {'mesh':8s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'coll_s':>10s} {'dominant':>10s} "
           f"{'useful':>6s} {'mem_GB':>7s}"]
    for r in recs:
        if not r.get("ok"):
            out.append(f"{r['arch']:24s} {r['shape']:12s} FAILED: "
                       f"{r.get('error', '')[:80]}")
            continue
        rf = r["roofline"]
        out.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
            f"{rf['compute_s']:10.3e} {rf['memory_s']:10.3e} "
            f"{rf['collective_s']:10.3e} {rf['dominant']:>10s} "
            f"{r['useful_flops_ratio']:6.2f} "
            f"{r['per_device_bytes']['total'] / 1e9:7.1f}")
    return out


def csv_rows(recs: List[Dict]) -> List[Dict]:
    rows = []
    for r in recs:
        if not r.get("ok"):
            continue
        rf = r["roofline"]
        rows.append({
            "name": f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            "us_per_call": rf["bound_s"] * 1e6,
            "derived": (f"dom={rf['dominant']};useful="
                        f"{r['useful_flops_ratio']:.2f}"),
        })
    return rows


if __name__ == "__main__":
    for line in table(load()):
        print(line)
