"""Concurrent query scheduler tests.

Parity — N concurrent queries admitted through the QueryScheduler must
produce bit-identical decisions/map values to running each sequentially,
and each query's StageStats must tile exactly (n_tuples / n_llm_calls /
n_batches per query equal to its solo run), across inline and threads
hub execution and 1- vs 2-engine pools. Cross-query coalescing merges
*batches*, never changes *schedules*, so this is the load-bearing
invariant of the whole subsystem.

Coalescing — K concurrent copies of one query must produce strictly
fewer engine attention dispatches than K solo runs (the merged batches
are real), while decisions stay bit-identical to solo.

Fairness / admission — weighted-fair virtual time orders admission
deterministically; the bounded queue raises SchedulerSaturated instead
of buffering unboundedly.

Tenants — premium tenants pre-warm the engines' device LRU (hits on the
first query), cold tenants evict their rungs after each query.
"""
import threading
import time

import numpy as np
import pytest

from repro.api import EngineSpec, Session, SessionConfig
from repro.core import PlannerConfig
from repro.core.physical import PhysicalOperator
from repro.data.synthetic import make_dataset
from repro.runtime import OracleBackend, backend_engines
from repro.scheduler import (QueryScheduler, SchedulerSaturated, TenantSpec,
                             split_ints, validate_tenants)
from repro.obs import FlushCounters
from repro.scheduler.hub import split_counters

FAST = PlannerConfig(steps=120, restarts=2, snapshots=2)
# scheduler tests exercise admission/coalescing, not plan quality — a
# tiny annealer keeps per-test planning time negligible
TINY = PlannerConfig(steps=40, restarts=1, snapshots=2)


# ---------------------------------------------------------------------------
# TenantSpec / split_ints units
# ---------------------------------------------------------------------------

def test_tenant_spec_validation():
    t = TenantSpec("acme", tier="premium")
    assert t.fair_weight == 4.0 and t.warms and not t.evicts
    assert TenantSpec("x", tier="cold").evicts
    assert TenantSpec("x", weight=2.5).fair_weight == 2.5
    assert TenantSpec("x", tier="cold", keep_warm=True).warms
    with pytest.raises(ValueError, match="tier"):
        TenantSpec("x", tier="platinum")
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("x", weight=0.0)
    with pytest.raises(ValueError, match="non-empty"):
        TenantSpec("")
    with pytest.raises(ValueError, match="duplicate"):
        validate_tenants((TenantSpec("a"), TenantSpec("a")))
    with pytest.raises(TypeError):
        validate_tenants(("a",))


def test_split_ints_tiles_exactly():
    for total, sizes in ((10, [3, 3, 4]), (7, [5, 5, 5]), (0, [1, 2]),
                         (13, [1]), (5, [0, 5]), (3, [])):
        out = split_ints(total, sizes)
        assert sum(out) == (total if sizes and sum(sizes) else 0)
        assert len(out) == len(sizes)
        assert all(v >= 0 for v in out)


def test_split_counters_tiles_exactly():
    """A merged call's flush counters split per query: integers exactly
    (split_ints), seconds by tuple share, and the parts sum back."""
    total = FlushCounters(read_s=0.3, assemble_s=1.2, h2d_s=0.9, wait_s=2.1,
                          prefetch_s=0.6, compile_s=0.5, compiles=3,
                          padded_bytes=1003, donated_bytes=97,
                          staged_reuses=5, staged_allocs=2,
                          lru_hit_bytes=10_007, lru_evictions=3)
    sizes = [37, 1, 0, 256]
    parts = split_counters(total, sizes)
    assert [p.padded_bytes for p in parts] == split_ints(1003, sizes)
    assert [p.staged_reuses for p in parts] == split_ints(5, sizes)
    assert [p.lru_hit_bytes for p in parts] == split_ints(10_007, sizes)
    assert [p.lru_evictions for p in parts] == split_ints(3, sizes)
    assert sum(p.compiles for p in parts) == 3
    summed = sum(parts, FlushCounters())
    for name, value in total.as_dict().items():
        assert getattr(summed, name) == pytest.approx(value, rel=1e-12)
    assert parts[0].wait_s == pytest.approx(2.1 * 37 / 294)
    assert parts[2] == FlushCounters()


def test_session_config_validates_tenants():
    cfg = SessionConfig(tenants=(TenantSpec("a"), TenantSpec("b")))
    assert [t.name for t in cfg.tenants] == ["a", "b"]
    with pytest.raises(ValueError, match="duplicate"):
        SessionConfig(tenants=(TenantSpec("a"), TenantSpec("a")))


# ---------------------------------------------------------------------------
# recording-operator world (no engine): fast, observable flushes
# ---------------------------------------------------------------------------

class _LogFilter(PhysicalOperator):
    uses_llm = True

    def __init__(self, name, task_id, log, lock, is_gold=False):
        self.name = name
        self.task_id = task_id
        self.log = log
        self.lock = lock
        self.is_gold = is_gold

    def run_filter(self, items, op):
        idx = np.asarray([it.item_id for it in items], np.float64)
        with self.lock:
            self.log.append(len(items))
        return np.asarray(
            3.0 * np.sin(idx * 12.9898 + op.task_id * 78.233), np.float32)


def _oracle_session():
    log, lock = [], threading.Lock()
    cheap = _LogFilter("cheap", 1, log, lock)
    gold = _LogFilter("gold", 2, log, lock, is_gold=True)
    sess = Session(backend=OracleBackend(lambda op: [cheap, gold]),
                   planner=TINY, sample_frac=0.5)
    return sess, log


def _frames(sess, ds, tasks=(1, 1, 2, 1)):
    return [(sess.frame(ds.items)
             .sem_filter(f"f{t}", task_id=t)
             .with_guarantees(recall=0.7, precision=0.7))
            for t in tasks]


@pytest.mark.parametrize("execute", ["inline", "threads:2"])
def test_concurrent_parity_oracle(execute):
    """N concurrent queries == their sequential runs, bit for bit, with
    exactly-tiling per-query stats, under both hub execution modes."""
    sess, log = _oracle_session()
    ds = make_dataset("sched-par", 90, seed=3)
    frames = _frames(sess, ds)
    solo = [f.execute() for f in frames]
    for f in frames:
        f.plan()                       # memoize plans: drivers admit fast
    with QueryScheduler(sess, max_concurrent=4, paused=True,
                        execute=execute) as sched:
        handles = [sched.submit(f) for f in frames]
        sched.resume()
        results = [h.result(timeout=120) for h in handles]
        stats = sched.stats()
    for r, s in zip(results, solo):
        np.testing.assert_array_equal(r.accepted, s.accepted)
        assert set(r.map_values) == set(s.map_values)
        for li in s.map_values:
            np.testing.assert_array_equal(r.map_values[li],
                                          s.map_values[li])
        # per-query stats tile exactly: counts identical to the solo run
        key = lambda sg: (sg.logical_idx, sg.stage, sg.op_name)
        mine = {key(sg): sg for sg in r.stage_stats}
        ref = {key(sg): sg for sg in s.stage_stats}
        assert set(mine) == set(ref)
        for k, sg in mine.items():
            assert sg.n_tuples == ref[k].n_tuples
            assert sg.n_llm_calls == ref[k].n_llm_calls
            assert sg.n_batches == ref[k].n_batches
    # the hub really executed every flush exactly once
    assert stats["n_flushes"] >= stats["n_calls"] > 0


def test_concurrent_copies_merge_flushes():
    """K concurrent copies of one query coalesce: fewer merged engine
    calls than total flushes, and every query's flushes ride shared
    batches whose width is the concatenation of the copies."""
    sess, log = _oracle_session()
    ds = make_dataset("sched-merge", 60, seed=5)
    frame = _frames(sess, ds, tasks=(1,))[0]
    solo = frame.execute()
    frame.plan()
    log.clear()
    K = 4
    with QueryScheduler(sess, max_concurrent=K, paused=True) as sched:
        handles = [sched.submit(frame) for _ in range(K)]
        sched.resume()
        results = [h.result(timeout=120) for h in handles]
        stats = sched.stats()
    for r in results:
        np.testing.assert_array_equal(r.accepted, solo.accepted)
    assert stats["n_merged_calls"] >= 1
    assert stats["n_calls"] < stats["n_flushes"]
    assert stats["saved_calls"] == stats["n_flushes"] - stats["n_calls"]
    # per-query telemetry observed the sharing
    assert any(r.sched.shared_batches > 0 for r in results)
    merged = [r for r in results if r.sched.shared_batches]
    for r in merged:
        assert r.sched.shared_width > r.sched.n_batches  # > own width


def test_weighted_fair_admission_order():
    """With one driver slot, admission replays weighted-fair virtual
    time: all tenants start at vtime 0 (arrival order breaks ties), and
    each completed query advances its tenant by tuples/weight — so the
    light tenant's second query waits until the heavy tenant's vtime
    catches up."""
    sess, _ = _oracle_session()
    ds = make_dataset("sched-fair", 40, seed=7)
    frame = _frames(sess, ds, tasks=(1,))[0]
    frame.plan()
    tenants = (TenantSpec("heavy", weight=4.0),
               TenantSpec("light", weight=1.0))
    with QueryScheduler(sess, max_concurrent=1, paused=True,
                        tenants=tenants) as sched:
        # interleaved submissions: h0 l1 h2 l3
        hs = [sched.submit(frame, tenant=t)
              for t in ("heavy", "light", "heavy", "light")]
        sched.resume()
        sched.drain(timeout=120)
        stats = sched.stats()
    order = sorted(range(4), key=lambda i: hs[i].admit_t)
    # q0 (heavy, tie at 0 broken by arrival) then q1 (light, vtime 0);
    # now heavy=n/4 < light=n, so q2 (heavy) before q3 (light)
    assert order == [0, 1, 2, 3]
    n = stats["tenants"]["heavy"]["n_tuples"]
    assert stats["tenants"]["heavy"]["vtime"] == pytest.approx(n / 4.0)
    assert stats["tenants"]["light"]["vtime"] == pytest.approx(
        stats["tenants"]["light"]["n_tuples"] / 1.0)


def test_admission_bounds_and_errors():
    sess, _ = _oracle_session()
    ds = make_dataset("sched-adm", 30, seed=2)
    frame = _frames(sess, ds, tasks=(1,))[0]
    frame.plan()
    with QueryScheduler(sess, max_concurrent=1, max_queue=2,
                        paused=True) as sched:
        h1 = sched.submit(frame)
        h2 = sched.submit(frame)
        with pytest.raises(SchedulerSaturated):
            sched.submit(frame)
        with pytest.raises(ValueError, match="unknown tenant"):
            sched.submit(frame, tenant="nobody")
        other = Session(backend=OracleBackend(
            lambda op: [_LogFilter("c", 1, [], threading.Lock()),
                        _LogFilter("g", 2, [], threading.Lock(),
                                   is_gold=True)]))
        with pytest.raises(ValueError, match="different Session"):
            sched.submit(other.frame(ds.items).sem_filter("f1", 1))
        other.close()
        sched.resume()
        assert h1.result(timeout=120).accepted is not None
        assert h2.result(timeout=120).accepted is not None
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(frame)


def test_handle_timeout_and_repr():
    sess, _ = _oracle_session()
    ds = make_dataset("sched-to", 30, seed=9)
    frame = _frames(sess, ds, tasks=(1,))[0]
    frame.plan()
    sched = QueryScheduler(sess, paused=True)
    h = sched.submit(frame)
    assert not h.done() and "queued" in repr(h)
    with pytest.raises(TimeoutError):
        h.result(timeout=0.01)
    sched.resume()
    assert h.result(timeout=120) is not None
    assert h.done() and "done" in repr(h)
    sched.close()


def test_query_error_propagates():
    """A failing operator fails that query's handle — it must not hang
    the hub or poison co-admitted queries."""
    boom = {"on": False}

    class _Bomb(_LogFilter):
        def run_filter(self, items, op):
            if boom["on"]:
                raise RuntimeError("operator exploded")
            return super().run_filter(items, op)

    log, lock = [], threading.Lock()
    # BOTH operators explode: the planner's cascade choice is profiled
    # from measured wall times, so whether a given plan keeps the cheap
    # stage is load-dependent — whichever stage fires first must raise
    cheap = _Bomb("cheap", 1, log, lock)
    gold = _Bomb("gold", 2, log, lock, is_gold=True)
    sess = Session(backend=OracleBackend(lambda op: [cheap, gold]),
                   planner=TINY, sample_frac=0.5)
    ds = make_dataset("sched-err", 40, seed=4)
    frame = _frames(sess, ds, tasks=(1,))[0]
    frame.plan()
    boom["on"] = True
    with QueryScheduler(sess, max_concurrent=2) as sched:
        h = sched.submit(frame)
        with pytest.raises(RuntimeError, match="exploded"):
            h.result(timeout=120)
    boom["on"] = False


def test_explain_analyze_scheduler_footer():
    sess, _ = _oracle_session()
    ds = make_dataset("sched-exp", 40, seed=6)
    frame = _frames(sess, ds, tasks=(1,))[0]
    frame.plan()
    with QueryScheduler(sess, paused=True,
                        tenants=(TenantSpec("acme", tier="premium"),)) \
            as sched:
        hs = [sched.submit(frame, tenant="acme") for _ in range(2)]
        sched.resume()
        reports = [h.result(timeout=120).explain_analyze() for h in hs]
    text = reports[0].render()
    assert "scheduler: tenant=acme (premium)" in text
    assert "queue_wait_s=" in text and "shared_batches=" in text


def test_scheduler_stress_many_small_queries():
    """Many overlapping small queries under the threads hub: all finish
    within the deadline (no deadlock), all bit-identical to solo."""
    sess, _ = _oracle_session()
    ds = make_dataset("sched-stress", 50, seed=11)
    frames = _frames(sess, ds, tasks=(1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3))
    solo = [f.execute() for f in frames]
    for f in frames:
        f.plan()
    t0 = time.monotonic()
    with QueryScheduler(sess, max_concurrent=6, execute="threads:3",
                        paused=True) as sched:
        handles = [sched.submit(f) for f in frames]
        sched.resume()
        results = [h.result(timeout=180) for h in handles]
    assert time.monotonic() - t0 < 180
    for r, s in zip(results, solo):
        np.testing.assert_array_equal(r.accepted, s.accepted)


# ---------------------------------------------------------------------------
# engine-backed worlds: real coalescing proof + tiered device cache
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_world(tmp_path_factory):
    ds = make_dataset("sched-eng", 48, seed=5)
    session = Session(SessionConfig(
        cache_dir=str(tmp_path_factory.mktemp("cache")),
        profile_ratios=(0.0, 0.8),
        sm_ratios=(0.8, 0.0), lg_ratios=(0.8,),
        planner=TINY, sample_frac=0.35))
    session.prepare(ds.items)
    yield ds, session
    session.close()


def _eng_frame(sess, ds):
    return (sess.frame(ds.items)
            .sem_filter("f1", 1)
            .with_guarantees(recall=0.7, precision=0.7))


def _total_dispatches(sess):
    return sum(e.attn_dispatches for e in backend_engines(sess.backend))


def test_engine_coalescing_reduces_dispatches(engine_world):
    """THE acceptance proof: K concurrent copies of one query drive
    strictly fewer engine attention dispatches than K solo runs, while
    every copy's decisions stay bit-identical to solo."""
    ds, sess = engine_world
    frame = _eng_frame(sess, ds)
    frame.plan()                            # plan+profiling outside count
    base = _total_dispatches(sess)
    solo = frame.execute()
    solo_dispatches = _total_dispatches(sess) - base
    assert solo_dispatches > 0
    K = 3
    base = _total_dispatches(sess)
    with QueryScheduler(sess, max_concurrent=K, paused=True) as sched:
        handles = [sched.submit(frame) for _ in range(K)]
        sched.resume()
        results = [h.result(timeout=600) for h in handles]
        stats = sched.stats()
    merged_dispatches = _total_dispatches(sess) - base
    for r in results:
        np.testing.assert_array_equal(r.accepted, solo.accepted)
    assert merged_dispatches < K * solo_dispatches
    assert stats["n_merged_calls"] >= 1
    # kv accounting still tiles: the K queries' kv_bytes sum to K x solo
    solo_kv = sum(sg.kv_bytes for sg in solo.stage_stats)
    merged_kv = sum(sg.kv_bytes for r in results
                    for sg in r.stage_stats)
    assert merged_kv == K * solo_kv


def test_premium_warm_and_cold_evict(engine_world, tmp_path):
    """Tiered tenants drive the engine device LRU: a premium tenant's
    first query pre-stages its rungs (device-cache hits during the run),
    a cold tenant's query evicts its rungs afterwards."""
    ds = make_dataset("sched-warm", 32, seed=8)
    sess = Session(SessionConfig(
        cache_dir=str(tmp_path / "cache"),
        profile_ratios=(0.0, 0.8),
        sm_ratios=(0.8, 0.0), lg_ratios=(0.8,),
        planner=TINY, sample_frac=0.35,
        device_cache=True,
        tenants=(TenantSpec("vip", tier="premium"),
                 TenantSpec("drifter", tier="cold"))))
    sess.prepare(ds.items)
    try:
        engines = backend_engines(sess.backend)
        assert all(e.device_cache for e in engines)
        frame = _eng_frame(sess, ds)
        frame.plan()
        with sess.scheduler(max_concurrent=1) as sched:
            h0 = sched.submit(frame, tenant="vip")
            r0 = h0.result(timeout=600)
            stats = sched.stats()
            assert stats["tenants"]["vip"]["warm_batches"] > 0
            # warming staged the rungs: the run itself hit the dev LRU
            assert sum(e.dev_cache_hits for e in engines) > 0
            assert sum(len(e._dev_cache) for e in engines) > 0
            h1 = sched.submit(frame, tenant="drifter")
            r1 = h1.result(timeout=600)
            stats = sched.stats()
            assert stats["tenants"]["drifter"]["evictions"] > 0
        np.testing.assert_array_equal(r0.accepted, r1.accepted)
    finally:
        sess.close()


@pytest.fixture(scope="module")
def pool_world(tmp_path_factory):
    ds = make_dataset("sched-pool", 48, seed=7)
    session = Session(SessionConfig(
        engines=(
            EngineSpec("fast", models=("sm",),
                       sm_ratios=(0.8, 0.0), lg_ratios=(),
                       cache_dir=str(tmp_path_factory.mktemp("fast"))),
            EngineSpec("accurate", models=("lg",),
                       sm_ratios=(), lg_ratios=(0.8,),
                       include_cheap=False,
                       cache_dir=str(tmp_path_factory.mktemp("accurate"))),
        ),
        gold_engine="accurate",
        planner=TINY, sample_frac=0.35))
    session.prepare(ds.items)
    yield ds, session
    session.close()


def test_concurrent_parity_two_engine_pool(pool_world):
    """Scheduler parity holds on a 2-engine pool: concurrent queries
    decide bit-identically to sequential, and per-engine flushes still
    coalesce (group keys carry the engine tag, so merging never mixes
    engines)."""
    ds, sess = pool_world
    frame = (sess.frame(ds.items)
             .sem_filter("f1", 1)
             .sem_map("extract v2", 2)
             .with_guarantees(recall=0.7, precision=0.7))
    solo = frame.execute()
    frame.plan()
    with QueryScheduler(sess, max_concurrent=3, paused=True) as sched:
        handles = [sched.submit(frame) for _ in range(3)]
        sched.resume()
        results = [h.result(timeout=600) for h in handles]
        stats = sched.stats()
    for r in results:
        np.testing.assert_array_equal(r.accepted, solo.accepted)
        for li in solo.map_values:
            np.testing.assert_array_equal(r.map_values[li],
                                          solo.map_values[li])
    assert stats["n_calls"] <= stats["n_flushes"]
    # stage stats still carry their owning engine after merging
    engs = {sg.engine for r in results for sg in r.stage_stats}
    assert "fast" in engs or "accurate" in engs


# ---------------------------------------------------------------------------
# hub patience: a slow member must not stall unrelated parked groups
# ---------------------------------------------------------------------------

def test_hub_patience_bounds_slow_member_stall():
    """While a fired group is still executing (a remote member on a bad
    link, say), a group parked AFTER the fire must wait at most the
    patience window — not the straggler's full service time. Under
    "threads" execution the late group overlaps the slow one."""
    from repro.runtime.dispatch import FlushTask
    from repro.scheduler import FlushHub

    log, lock = [], threading.Lock()

    class _SleepFilter(_LogFilter):
        def __init__(self, name, task_id, delay):
            super().__init__(name, task_id, log, lock)
            self.delay = delay

        def run_filter(self, items, op):
            time.sleep(self.delay)
            return super().run_filter(items, op)

    slow = _SleepFilter("slow", 1, 1.2)
    fast = _SleepFilter("fast", 2, 0.0)
    backend = OracleBackend(lambda op: [slow, fast])
    ds = make_dataset("hub-slow", 20, seed=1)
    hub = FlushHub(backend, execute="threads:2", patience_s=0.05)
    elapsed = {}
    errors = []

    def driver(name, op_name, sem, start_delay):
        hub.register()
        try:
            time.sleep(start_delay)
            task = FlushTask(0, sem, op_name, list(ds.items), "")
            t0 = time.monotonic()
            out = hub.submit(name, task).result()
            elapsed[name] = time.monotonic() - t0
            assert len(out.scores) == len(ds.items)
        except BaseException as e:            # surface into the test
            errors.append(e)
        finally:
            hub.unregister()

    from repro.core.logical import SemFilter
    ta = threading.Thread(target=driver,
                          args=("a", "slow", SemFilter("s", 1), 0.0))
    # driver b parks its flush only after a's slow group has fired
    tb = threading.Thread(target=driver,
                          args=("b", "fast", SemFilter("f", 2), 0.3))
    ta.start(), tb.start()
    ta.join(timeout=30), tb.join(timeout=30)
    hub.close()
    assert not errors
    # the fast group waited ~patience, not ~the slow member's 1.2 s
    assert elapsed["b"] < 0.6
    assert elapsed["a"] >= 1.0
    snap = hub.snapshot()
    assert snap["n_calls"] == 2 and snap["n_flushes"] == 2


def test_split_ints_remainder_on_leading_segments():
    """Retry-shaped splits (a sub-batch re-issued at a different width)
    still tile exactly: sum preserved, remainder on the leading
    segments, zero-width segments get zero."""
    assert split_ints(10, [3, 3, 3]) == [4, 3, 3]
    assert split_ints(11, [3, 3, 3]) == [4, 4, 3]
    assert split_ints(1003, [37, 1, 0, 256]) == [127, 3, 0, 873]
    for total, sizes in ((1003, [37, 1, 0, 256]), (97, [64, 1, 64]),
                         (5, [1, 1, 1, 1, 1, 1, 1])):
        out = split_ints(total, sizes)
        assert sum(out) == total
        assert all(v >= 0 for v in out)
        # remainder lands on the leading segments: the split is the
        # floor apportionment plus at most 1 on a leading prefix
        n = sum(sizes)
        floors = [total * s // n for s in sizes]
        bumps = [o - f for o, f in zip(out, floors)]
        assert set(bumps) <= {0, 1}
        assert bumps == sorted(bumps, reverse=True)
