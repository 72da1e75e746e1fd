"""Benchmark entry point — one harness per paper table/figure.

Default (no args) runs a bounded configuration suitable for CI/CPU
(~10-20 min): 2 datasets at 30% scale, 3 queries per (dataset, target).
``--full`` approaches paper scale (5 datasets, more queries); ``--smoke``
is the CI perf-trajectory job: one tiny dataset, one query per target,
kernel/roofline sections skipped, and the run self-validates that the
written ``stage_stats-<ts>-<sha>.json`` snapshot parses and carries
non-zero measured mean batches (exit 1 otherwise) — so the trajectory
artifact can never silently go empty.

Prints a ``name,us_per_call,derived`` CSV plus human-readable summaries,
including the planned-vs-measured batch drift the measured-feedback loop
is meant to close.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "nogit"
    except (OSError, subprocess.SubprocessError):
        return "nogit"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny self-validating run for the CI trajectory "
                         "artifact")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--out", type=str, default="results/bench")
    args = ap.parse_args()

    import repro
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (exp1_accuracy_runtime as E1,
                            exp2_kv_cache as E2, exp3_global_local as E3,
                            kernels_bench, roofline)
    from benchmarks.common import build_world

    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    if args.smoke:
        scale = args.scale or 0.1
        names = ("movies",)
        nq = 1
        targets = (0.7,)
        cfg = repro.PlannerConfig(steps=120, restarts=2, snapshots=2)
    else:
        scale = args.scale or (1.0 if args.full else 0.25)
        names = None if args.full else ("movies", "artwork")
        nq = 6 if args.full else 2
        targets = (0.5, 0.7, 0.9) if args.full else (0.7, 0.9)
        cfg = repro.PlannerConfig(steps=300 if args.full else 200,
                                  restarts=4 if args.full else 3)

    print(f"# building world (scale={scale}) ...", flush=True)
    world = build_world(scale=scale, dataset_names=names,
                        config=repro.SessionConfig(planner=cfg))

    csv_rows = []
    stage_stats = []   # per-stage StageStats across all experiments: the
    #                    perf-trajectory artifact future PRs diff against

    print("# exp1 (Fig 5): guarantees + runtime vs baselines", flush=True)
    rows1 = E1.run(world, targets=targets, n_queries=nq, planner_cfg=cfg)
    for r in rows1:
        stage_stats += r.pop("stage_stats", [])
    with open(f"{args.out}/exp1.json", "w") as f:
        json.dump(rows1, f, indent=1)
    for line in E1.summarize(rows1):
        print(line)
    for method in ("stretto", "lotus", "pareto"):
        sub = [r for r in rows1 if r["method"] == method]
        if sub:
            csv_rows.append({
                "name": f"exp1_runtime_{method}",
                "us_per_call": float(np.median(
                    [r["runtime_s"] for r in sub])) * 1e6,
                "derived": f"met={np.mean([(r['target_met_recall'] >= 1) & (r['target_met_precision'] >= 1) for r in sub]):.2f}"})

    print("# exp2 (Fig 6/Table 1/Fig 7): KV-cache operators", flush=True)
    first_ds = next(iter(world.datasets))
    lad = E2.ladder(world, first_ds)
    spd = E2.speedup_with_compression(world, targets=targets,
                                      n_queries=max(nq - 1, 1),
                                      planner_cfg=cfg)
    for r in spd:
        stage_stats += r.pop("stage_stats", [])
    with open(f"{args.out}/exp2.json", "w") as f:
        json.dump({"ladder": lad, "speedup": spd}, f, indent=1)
    for line in E2.summarize(lad, spd):
        print(line)
    csv_rows.append({
        "name": "exp2_speedup_with_compression",
        "us_per_call": 0.0,
        "derived": f"avg={np.mean([r['speedup'] for r in spd]):.2f}x"})

    print("# exp3 (Fig 8): global vs local vs independent", flush=True)
    rows3 = E3.run(world, targets=targets, n_queries=max(nq - 1, 1),
                   planner_cfg=cfg)
    for r in rows3:
        stage_stats += r.pop("stage_stats", [])
    with open(f"{args.out}/exp3.json", "w") as f:
        json.dump(rows3, f, indent=1)
    for line in E3.summarize(rows3):
        print(line)

    # perf-trajectory artifact: stage_stats.json is always the latest run
    # (stable name for tooling), and every run ALSO lands in its own
    # timestamped snapshot so the trajectory accumulates across commits
    # instead of being clobbered
    meta = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_sha": _git_sha(),
        "dispatcher": os.environ.get("STRETTO_DISPATCHER", "") or "inline",
        "scale": scale,
        "full": bool(args.full),
    }
    with open(f"{args.out}/stage_stats.json", "w") as f:
        json.dump(stage_stats, f, indent=1)
    snap = (f"{args.out}/stage_stats-"
            f"{time.strftime('%Y%m%dT%H%M%S')}-{meta['git_sha']}.json")
    with open(snap, "w") as f:
        json.dump({"meta": meta, "stages": stage_stats}, f, indent=1)
    by_op = {}
    for r in stage_stats:
        d = by_op.setdefault(r["op_name"], dict(wall_s=0.0, n_tuples=0,
                                                kv_bytes=0, n_batches=0))
        d["wall_s"] += r["wall_s"]
        d["n_tuples"] += r["n_tuples"]
        d["kv_bytes"] += r["kv_bytes"]
        d["n_batches"] += r["n_batches"]
    print(f"# stage stats -> {args.out}/stage_stats.json and {snap} "
          f"({len(stage_stats)} stage records, "
          f"dispatcher={meta['dispatcher']})")
    for op, d in sorted(by_op.items()):
        us = d["wall_s"] / max(d["n_tuples"], 1) * 1e6
        mean_b = d["n_tuples"] / max(d["n_batches"], 1)
        csv_rows.append({"name": f"stage_{op}", "us_per_call": us,
                         "derived": f"tuples={d['n_tuples']} "
                                    f"kvMB={d['kv_bytes'] / 1e6:.1f} "
                                    f"batches={d['n_batches']} "
                                    f"meanb={mean_b:.1f}"})

    # planned-vs-measured convergence: how far measured flush batches sat
    # from the planner's expectations, across every stage that recorded a
    # planned_batch (the quantity the measured-feedback loop closes)
    drifts = [r["batch_drift"] for r in stage_stats
              if r.get("batch_drift")]
    if drifts:
        logs = np.abs(np.log2(np.maximum(drifts, 1e-9)))
        print(f"# batch model: {len(drifts)} stages with planned batch, "
              f"median |log2 drift|={np.median(logs):.2f} "
              f"p90={np.percentile(logs, 90):.2f} "
              f"(0 = planner predicted measured flush widths exactly)")
        csv_rows.append({
            "name": "planned_vs_measured_batch",
            "us_per_call": 0.0,
            "derived": f"median_abs_log2_drift={np.median(logs):.3f} "
                       f"n={len(drifts)}"})

    if not args.smoke:
        print("# kernel microbenches", flush=True)
        krows = kernels_bench.run()
        csv_rows.extend(krows)

        print("# roofline (from dry-run artifacts, if present)", flush=True)
        recs = roofline.load("results/dryrun_sp")
        if recs:
            for line in roofline.table(recs)[:40]:
                print(line)
            csv_rows.extend(roofline.csv_rows(recs))
        else:
            print("  (run `python -m repro.launch.dryrun --all --out "
                  "results/dryrun_sp` first)")

    print("\nname,us_per_call,derived")
    for r in csv_rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    print(f"\n# total benchmark wall time: {time.time() - t0:.0f}s")
    world.close()

    if args.smoke:
        _smoke_check(snap)


def _smoke_check(snapshot_path: str) -> None:
    """CI gate: the trajectory snapshot must be parseable and carry real
    measurements — a run that produced an empty or degenerate snapshot
    must fail loudly, not silently upload a useless artifact."""
    with open(snapshot_path) as f:
        snap = json.load(f)
    stages = snap.get("stages", [])
    assert stages, f"{snapshot_path}: no stage records"
    assert all(r.get("n_batches", 0) >= 1 for r in stages), \
        f"{snapshot_path}: stage record with no flushes"
    # every stage row must carry its engine placement (exp2 aggregates
    # KV bytes per engine from it; "" marks single-engine sessions)
    assert all("engine" in r for r in stages), \
        f"{snapshot_path}: stage record missing the engine field"
    mean_batches = [r.get("mean_batch", 0) for r in stages]
    assert any(b > 0 for b in mean_batches), \
        f"{snapshot_path}: all mean_batch zero"
    assert snap.get("meta", {}).get("git_sha"), \
        f"{snapshot_path}: missing meta.git_sha"
    n_planned = sum(1 for r in stages if r.get("planned_batch"))
    print(f"# smoke check ok: {snapshot_path} ({len(stages)} stage "
          f"records, {n_planned} with planned-vs-measured batch, "
          f"max mean_batch={max(mean_batches):.1f})")


if __name__ == "__main__":
    main()
