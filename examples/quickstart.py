"""Quickstart: Stretto end to end through the declarative API.

One `Session` owns the whole engine lifecycle (cache store, planted
models, KV-cache profile building — the paper's offline phase, backend
and dispatcher resolution); a lazy `SemFrame` declares the query and its
end-to-end quality guarantees once. `explain()` shows the planned
cascade before anything runs, `execute()` runs it through the streaming
runtime, `metrics()` lazily compares against the gold reference, and
`stream()` delivers per-partition results incrementally.

    PYTHONPATH=src python examples/quickstart.py           # one engine
    PYTHONPATH=src python examples/quickstart.py --pool    # two-tier pool
    PYTHONPATH=src python examples/quickstart.py --remote  # wire-served tier

``--pool`` declares a heterogeneous engine pool instead of the flat
single-engine config: a "fast" tier serving the small model's compression
ladder and an "accurate" tier serving the large model (and the gold
reference). The planner places every cascade stage on one engine —
EXPLAIN grows an `engine` column, and EXPLAIN ANALYZE reports measured
per-engine cost and KV bytes that sum exactly to the session totals.

``--remote`` serves the fast tier from a real worker subprocess on
127.0.0.1 (`EngineSpec(address=...)`): the same plan decides
bit-identically to the all-local pool, EXPLAIN ANALYZE grows a
``remote:`` wire-telemetry footer — then the worker is SIGKILLed
mid-stream and the run completes on the gold engine via the
``on_unavailable="fallback"`` degradation policy.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import repro
from repro.data.synthetic import make_dataset
from repro.launch.compile_cache import enable_compile_cache


def single_engine_config() -> "repro.SessionConfig":
    return repro.SessionConfig(
        profile_ratios=(0.0, 0.3, 0.5, 0.8),     # offline cache ladder
        sm_ratios=(0.8, 0.5, 0.0),               # cascade candidates
        lg_ratios=(0.8, 0.5, 0.3),
        planner=repro.PlannerConfig(steps=200, restarts=3),
        sample_frac=0.25,
        partition_size=64,                       # streaming execution
    )


def pool_config() -> "repro.SessionConfig":
    """A two-tier engine pool: cheap sm tier + accurate lg tier (which
    also owns the gold reference operator)."""
    return repro.SessionConfig(
        engines=(
            repro.EngineSpec("fast", models=("sm",),
                             sm_ratios=(0.8, 0.5, 0.0), lg_ratios=()),
            repro.EngineSpec("accurate", models=("lg",),
                             sm_ratios=(), lg_ratios=(0.5, 0.3),
                             include_cheap=False),
        ),
        gold_engine="accurate",
        planner=repro.PlannerConfig(steps=200, restarts=3),
        sample_frac=0.25,
        partition_size=64,
    )


def run_remote(ds) -> None:
    """The --pool topology with the fast tier behind a real subprocess
    worker: bit-parity with all-local, the EXPLAIN ANALYZE wire footer,
    and graceful degradation when the worker is SIGKILLed mid-run."""
    import signal

    import numpy as np

    from repro.remote.client import remote_members
    from repro.remote.testing import spawn_worker

    local_cfg = pool_config()
    print("launching loopback worker (builds its ladder on first sync)...")
    proc, addr = spawn_worker(name="fast", models=("sm",),
                              sm_ratios=(0.8, 0.5, 0.0), lg_ratios=())
    remote_cfg = repro.SessionConfig(
        engines=(repro.EngineSpec("fast", address=addr),
                 local_cfg.engines[1]),         # same accurate/gold tier
        gold_engine="accurate",
        planner=repro.PlannerConfig(steps=200, restarts=3),
        sample_frac=0.25, partition_size=64)
    try:
        with repro.Session(local_cfg) as ls, \
                repro.Session(remote_cfg) as rs:
            frame = (ls.frame(ds)
                     .sem_filter("mentions topic 1", task_id=1)
                     .sem_map("extract field 2", task_id=2)
                     .with_guarantees(recall=0.75, precision=0.75))
            query = frame.to_query()
            plan = ls.plan(query, ds.items)

            # --- parity: one plan, two pools, identical bits -----------
            lr = ls.run(plan, query, ds.items, dispatcher="inline")
            rr = rs.run(plan, query, ds.items, dispatcher="inline")
            same = (np.array_equal(rr.accepted, lr.accepted)
                    and all(np.array_equal(rr.map_values[li],
                                           lr.map_values[li])
                            for li in lr.map_values))
            print(f"decisions bit-identical to all-local: {same}")
            assert same, "remote parity broke"
            w = rr.remote
            print(f"wire: {w['calls']} calls, {w['wire_kb']:.1f} KiB, "
                  f"rtt p50 {w['rtt_ms_p50']:.2f}ms "
                  f"p95 {w['rtt_ms_p95']:.2f}ms")

            # --- EXPLAIN ANALYZE grows the remote footer ---------------
            res = (rs.frame(ds)
                   .sem_filter("mentions topic 1", task_id=1)
                   .sem_map("extract field 2", task_id=2)
                   .with_guarantees(recall=0.75, precision=0.75)
                   .execute())
            print(res.explain_analyze())

            # --- SIGKILL mid-stream: degrade onto the gold engine ------
            member = remote_members(rs.backend)[0]
            gen = rs.iter_run(plan, query, ds.items, partition_size=50,
                              coalesce=1, dispatcher="inline")
            next(gen)                        # first partition on the wire
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait()
            print("worker SIGKILLed mid-stream; draining on the gold "
                  "fallback...")
            try:
                while True:
                    next(gen)
            except StopIteration as stop:
                result = stop.value
            snap = member.snapshot()
            print(f"degraded run completed: "
                  f"{int(result.accepted.sum())} accepted, "
                  f"fallbacks={snap['fallbacks']}, "
                  f"retries={snap['retries']}")
            assert snap["fallbacks"] > 0, "no flush fell back to gold"
    finally:
        proc.kill()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pool", action="store_true",
                    help="declare a two-tier heterogeneous engine pool")
    ap.add_argument("--remote", action="store_true",
                    help="serve the fast tier from a loopback worker "
                         "subprocess, then SIGKILL it mid-run")
    args = ap.parse_args()
    enable_compile_cache()
    ds = make_dataset("quickstart", 200, seed=3)
    if args.remote:
        run_remote(ds)
        return
    config = pool_config() if args.pool else single_engine_config()
    with repro.Session(config) as sess:
        # --- a semantic query with global quality targets, declared once
        frame = (sess.frame(ds)
                 .sem_filter("mentions topic 1", task_id=1)
                 .sem_map("extract field 2", task_id=2)
                 .with_guarantees(recall=0.75, precision=0.75))

        # --- EXPLAIN: the planned cascade, before anything executes ----
        print(frame.explain())

        # --- execute through the streaming runtime ---------------------
        # (the first execution pays jit compilation for every selected
        # operator/batch shape; re-running warm measures steady state,
        # which is what the planner's profiled costs model)
        frame.execute()
        res = frame.execute()
        m = res.metrics()                        # lazy gold comparison
        print(f"quality vs gold: precision={m['precision']:.3f} "
              f"recall={m['recall']:.3f} (targets 0.75)")
        print(f"runtime: {res.runtime_s:.2f}s operator time, "
              f"{res.wall_s:.2f}s elapsed "
              f"-> speedup {res.speedup_vs_gold():.2f}x vs gold "
              f"({res.n_partitions} partitions)")

        # --- EXPLAIN ANALYZE: planned vs measured, side by side --------
        print(res.explain_analyze())

        if args.pool:
            # per-engine measured totals partition the run exactly
            for eng, d in sorted(res.engine_totals().items()):
                print(f"engine {eng}: {d['n_tuples']} tuples, "
                      f"{d['n_llm_calls']} LLM calls, "
                      f"{d['kv_bytes'] / 1e6:.1f} MB KV loaded")

        # --- streaming: consume partitions as they settle --------------
        print("streaming the same query, 50 tuples per partition:")
        stream = frame.stream(partition_size=50)
        for part in stream:
            print(f"  partition {part.index} [{part.lo}:{part.hi}) "
                  f"-> {int(part.accepted.sum())} accepted "
                  f"({stream.progress:.0%} settled, "
                  f"{sum(s.n_llm_calls for s in stream.stage_stats)} "
                  f"LLM calls so far)")


if __name__ == "__main__":
    main()
