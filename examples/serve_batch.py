"""Concurrent serving example: overlapping SemFrame queries through the
QueryScheduler, sharing one Session's engine pool.

A `Session` owns the offline phase (cache store, model registration,
profile building for the ladder); the scheduler then admits many
declarative queries at once — flushes from different queries that target
the same (engine, operator) coalesce into merged engine calls, tiered
tenants get weighted-fair shares and device-cache treatment, and each
result carries its own scheduler telemetry.

    PYTHONPATH=src python examples/serve_batch.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

import repro
from repro.cache.store import Profile
from repro.data.synthetic import make_dataset
from repro.launch.compile_cache import enable_compile_cache

RATIOS = (0.0, 0.5, 0.8)


def main():
    enable_compile_cache()
    ds = make_dataset("serve", 200, seed=9)
    config = repro.SessionConfig(
        memory_budget_bytes=5e8,
        profile_ratios=RATIOS,
        sm_ratios=RATIOS, lg_ratios=RATIOS,
        tenants=(repro.TenantSpec("analytics", tier="premium"),
                 repro.TenantSpec("adhoc"),
                 repro.TenantSpec("backfill", tier="cold")))
    with repro.Session(config) as sess:
        t0 = time.time()
        sess.prepare(ds.items)                   # offline phase
        print(f"offline: caches for {len(ds.items)} items x "
              f"{len(config.models)} models x {len(RATIOS)} ratios "
              f"in {time.time() - t0:.1f}s")
        for size in config.models:
            for r in RATIOS:
                mb = sess.engine.store.storage_bytes(
                    Profile(size, r)) / 1e6
                print(f"  profile {size}-r{r}: {mb:.1f} MB on disk")

        # overlapping declarative queries: three tenants, six queries —
        # identical queries coalesce their engine flushes when admitted
        # together
        frames = [
            (sess.frame(ds.items)
             .sem_filter(f"filter task {t}", task_id=t)
             .with_guarantees(recall=0.7, precision=0.7))
            for t in (1, 1, 2, 2, 3, 1)
        ]
        tenants = ("analytics", "adhoc", "analytics",
                   "backfill", "adhoc", "analytics")
        print(f"\nsubmitting {len(frames)} overlapping queries:")
        t0 = time.time()
        with sess.scheduler(max_concurrent=len(frames)) as sched:
            sched.pause()                  # admit the batch all at once
            handles = [sched.submit(f, tenant=tn)
                       for f, tn in zip(frames, tenants)]
            sched.resume()
            for h in handles:
                res = h.result(timeout=600)
                s = res.sched
                print(f"  q{s.query_id} [{s.tenant}/{s.tier}]: "
                      f"{int(res.accepted.sum())}/{len(ds.items)} "
                      f"accepted, wait={s.queue_wait_s * 1e3:.0f}ms, "
                      f"shared_batches={s.shared_batches}")
            stats = sched.stats()
        wall = time.time() - t0
        print(f"\n{len(frames)} queries in {wall:.1f}s "
              f"({len(frames) / max(wall, 1e-9):.2f} q/s): "
              f"{stats['n_flushes']} flushes -> {stats['n_calls']} "
              f"engine calls ({stats['saved_calls']} saved by "
              f"cross-query coalescing)")
        for name, t in sorted(stats["tenants"].items()):
            if t["n_queries"]:
                print(f"  {name} ({t['tier']}, w={t['weight']}): "
                      f"{t['n_queries']} queries, vtime={t['vtime']:.0f}, "
                      f"warm_batches={t['warm_batches']}, "
                      f"evictions={t['evictions']}")


if __name__ == "__main__":
    main()
