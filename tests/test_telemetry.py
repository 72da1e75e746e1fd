"""Truthful-telemetry tests: per-partition StageStats tiling, wall_s vs
runtime_s under parallel dispatch, EXPLAIN ANALYZE measured columns, the
MeasuredBatchStore measure->plan loop and replan-on-drift.

The worlds here are pure-python recording/sleeping operators (no engine),
so counts are observable and parallel speedup is deterministic enough to
assert on; engine-backed KV-bytes parity lives in tests/test_api.py where
the profile-built session fixture already exists.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.api import Session
from repro.core import MeasuredBatchStore, PlannerConfig, Query, \
    SemFilter, SemMap, batch_drift
from repro.core.physical import (PhysicalOperator, PhysicalPlan,
                                 PhysicalPlanStage)
from repro.obs import FlushCounters
from repro.runtime import OracleBackend, as_backend, iter_plan, run_plan
from repro.runtime.executor import StageStats, merge_stage_stats

FASTCFG = PlannerConfig(steps=120, restarts=2, snapshots=2)
FAST = dict(planner=FASTCFG, sample_frac=0.5)


class _Item:
    __slots__ = ("idx", "row")

    def __init__(self, idx: int):
        self.idx = idx
        self.row = {}


def _score(idx, task_id, scale=3.0):
    return np.float32(
        scale * np.sin(np.asarray(idx, np.float64) * 12.9898
                       + task_id * 78.233))


class _Filter(PhysicalOperator):
    uses_llm = True

    def __init__(self, name, task_id, is_gold=False, sleep_s=0.0):
        self.name = name
        self.task_id = task_id
        self.is_gold = is_gold
        self.sleep_s = sleep_s

    def run_filter(self, items, op):
        if self.sleep_s:
            time.sleep(self.sleep_s)
        return _score([it.idx for it in items], self.task_id)


class _Map(PhysicalOperator):
    uses_llm = True

    def __init__(self, name, task_id, is_gold=False):
        self.name = name
        self.task_id = task_id
        self.is_gold = is_gold

    def run_filter(self, items, op):
        raise NotImplementedError

    def run_map(self, items, op):
        idx = [it.idx for it in items]
        return (np.asarray(idx, np.int64) % 5, _score(idx, self.task_id))


def _world(sleep_s=0.0):
    """A 2-stage filter cascade + 2-stage map cascade with a hand-built
    plan (no planner), so telemetry shape is fully deterministic."""
    f_cheap = _Filter("f-cheap", 1, sleep_s=sleep_s)
    f_gold = _Filter("f-gold", 2, is_gold=True, sleep_s=sleep_s)
    m_cheap = _Map("m-cheap", 3)
    m_gold = _Map("m-gold", 4, is_gold=True)
    sf, sm = SemFilter("f", 1), SemMap("m", 3)

    def registry(op):
        return [f_cheap, f_gold] if isinstance(op, SemFilter) \
            else [m_cheap, m_gold]

    q = Query([sf, sm], target_recall=0.8, target_precision=0.8)
    stages = [
        PhysicalPlanStage(0, 0, "f-cheap", 1.0, -1.0, False, False, 0.1,
                          exp_batch=16.0),
        PhysicalPlanStage(1, 0, "m-cheap", 1.5, -np.inf, True, False, 0.1,
                          exp_batch=16.0),
        PhysicalPlanStage(0, 1, "f-gold", 0.0, 0.0, False, True, 1.0,
                          exp_batch=8.0),
        PhysicalPlanStage(1, 1, "m-gold", 0.0, 0.0, True, True, 1.0,
                          exp_batch=8.0),
    ]
    plan = PhysicalPlan(stages, [], 0.0, 1.0, 1.0, True)
    return q, plan, registry


def _stats_by_key(stats):
    return {(s.logical_idx, s.stage, s.op_name): s for s in stats}


class _CountingBackend(OracleBackend):
    """The oracle operators plus a per-thread FlushCounters set that grows
    with the tuples each flush scores, as a serving engine's does."""

    PER_TUPLE = FlushCounters(read_s=1e-4, assemble_s=3e-3, h2d_s=2e-3,
                              layout_s=4e-4, wait_s=5e-3, prefetch_s=1e-3,
                              compile_s=7e-4, compiles=1, padded_bytes=96,
                              donated_bytes=64, staged_reuses=1,
                              staged_allocs=2, lru_hit_bytes=48,
                              lru_evictions=1)

    def __init__(self, registry):
        super().__init__(registry)
        self._tl = threading.local()

    def _count(self, n):
        per = self.PER_TUPLE.as_dict()
        self._tl.c = self.transfer_stats() + FlushCounters(
            **{k: v * n for k, v in per.items()})

    def transfer_stats(self):
        return getattr(self._tl, "c", FlushCounters())

    def score_filter(self, op, op_name, items):
        self._count(len(items))
        return super().score_filter(op, op_name, items)

    def run_map(self, op, op_name, items):
        self._count(len(items))
        return super().run_map(op, op_name, items)


BACKENDS = {"oracle": as_backend, "flush-counters": _CountingBackend}


# ---------------------------------------------------------------------------
# per-partition StageStats tile the run's final stats exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("dispatcher", ["inline", "threads:3", "sharded:3"])
@pytest.mark.parametrize("part", [7, 20, None])
def test_partition_stats_sum_to_final(dispatcher, part, backend):
    items = [_Item(i) for i in range(53)]
    q, plan, registry = _world()
    gen = iter_plan(plan, q, items, BACKENDS[backend](registry),
                    partition_size=part, coalesce=13, dispatcher=dispatcher)
    parts = []
    while True:
        try:
            parts.append(next(gen))
        except StopIteration as stop:
            final = stop.value
            break
    assert parts, "no partitions emitted"
    # integer counters tile bit-exactly; wall times up to summation order
    merged = _stats_by_key(merge_stage_stats(
        [p.stage_stats for p in parts], plan))
    fin = _stats_by_key(final.stage_stats)
    assert set(merged) == set(fin)
    for key, sg in fin.items():
        m = merged[key]
        assert m.n_tuples == sg.n_tuples, key
        assert m.n_llm_calls == sg.n_llm_calls, key
        assert m.n_batches == sg.n_batches, key
        assert m.kv_bytes == sg.kv_bytes, key
        assert m.wall_s == pytest.approx(sg.wall_s, rel=1e-9), key
        # the flush counter set tiles the same way: integers exactly,
        # seconds up to summation order
        for name, value in sg.flush.as_dict().items():
            got = getattr(m.flush, name)
            if isinstance(value, int):
                assert got == value, (key, name)
            else:
                assert got == pytest.approx(value, rel=1e-9), (key, name)
    # and the counts themselves are real: every corpus tuple was scored
    # by the first stage exactly once
    first = fin[(0, 0, "f-cheap")]
    assert first.n_tuples == len(items)
    per = _CountingBackend.PER_TUPLE if backend == "flush-counters" \
        else FlushCounters()
    assert first.flush.padded_bytes == per.padded_bytes * len(items)
    assert first.flush.wait_s == pytest.approx(per.wait_s * len(items))


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_final_stage_counters_bit_identical_across_dispatchers(backend):
    """The *final* integer counters are dispatcher-invariant: every stage
    scores exactly the same tuple set under any dispatcher (the flush
    membership invariant), so n_tuples / n_llm_calls / kv_bytes must be
    bit-identical across inline, threads and sharded. Only the grouping
    of that work into flush batches (n_batches) and its per-partition
    attribution may move with the schedule — per-tuple totals never do."""
    items = [_Item(i) for i in range(41)]

    def run(disp):
        q, plan, registry = _world()
        return run_plan(plan, q, items, BACKENDS[backend](registry),
                        partition_size=9, coalesce=11, dispatcher=disp)

    ref = _stats_by_key(run("inline").stage_stats)
    for disp in ("threads:3", "sharded:3"):
        got = _stats_by_key(run(disp).stage_stats)
        assert set(got) == set(ref), disp
        for key in ref:
            assert got[key].n_tuples == ref[key].n_tuples, (disp, key)
            assert got[key].n_llm_calls == ref[key].n_llm_calls, (disp, key)
            assert got[key].kv_bytes == ref[key].kv_bytes, (disp, key)
            for name, value in ref[key].flush.as_dict().items():
                if isinstance(value, int):
                    assert getattr(got[key].flush, name) == value, \
                        (disp, key, name)


# ---------------------------------------------------------------------------
# wall_s vs runtime_s
# ---------------------------------------------------------------------------

def test_wall_s_measures_elapsed_not_summed_time():
    items = [_Item(i) for i in range(48)]
    q, plan, registry = _world(sleep_s=0.005)
    # serial: elapsed covers every operator call plus scheduling overhead
    rr = run_plan(plan, q, items, as_backend(registry),
                  partition_size=8, dispatcher="inline")
    assert rr.wall_s >= rr.runtime_s > 0
    # parallel scatter: summed operator time stays ~the serial total, but
    # elapsed wall clock must drop strictly below it — the speedup the
    # old summed-only accounting could not show. A first scatter compiles
    # the decision kernel at the shard widths; that one-off compile is not
    # operator time, so it runs before the measured scatter.
    run_plan(plan, q, items, as_backend(registry), partition_size=8,
             dispatcher="sharded:4")
    rs = run_plan(plan, q, items, as_backend(registry),
                  partition_size=8, dispatcher="sharded:4")
    assert rs.n_workers == 4
    assert 0 < rs.wall_s < rs.runtime_s


def test_sharded_partition_carries_shard_stats_and_wall():
    items = [_Item(i) for i in range(30)]
    q, plan, registry = _world(sleep_s=0.002)
    gen = iter_plan(plan, q, items, as_backend(registry),
                    dispatcher="sharded:3")
    parts = []
    while True:
        try:
            parts.append(next(gen))
        except StopIteration as stop:
            final = stop.value
            break
    assert len(parts) == 3
    for p in parts:
        assert p.stage_stats and p.wall_s > 0
        assert sum(s.n_tuples for s in p.stage_stats
                   if s.op_name == "f-cheap") == len(p)
    assert sum(s.n_tuples for p in parts for s in p.stage_stats) == \
        sum(s.n_tuples for s in final.stage_stats)


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE via the Session API
# ---------------------------------------------------------------------------

def _session_world():
    f_cheap = _Filter("f-cheap", 1)
    f_gold = _Filter("f-gold", 2, is_gold=True)
    sess = Session(backend=OracleBackend(
        lambda op: [f_cheap, f_gold]), **FAST)
    items = [_Item(i) for i in range(60)]
    return sess, items


def test_explain_analyze_matches_stage_stats():
    sess, items = _session_world()
    frame = (sess.frame(items).sem_filter("f", task_id=1)
             .with_guarantees(recall=0.7, precision=0.7))
    plain = frame.explain()
    assert not plain.analyzed
    assert "EXPLAIN ANALYZE" not in plain.render()

    res = frame.execute(partition_size=16)
    rep = res.explain_analyze()
    assert rep.analyzed
    assert rep.measured_runtime_s == pytest.approx(res.runtime_s)
    assert rep.measured_wall_s == pytest.approx(res.wall_s)
    assert rep.measured_partitions == res.n_partitions

    measured = _stats_by_key(res.stage_stats)
    seen = 0
    for st in rep.stages:
        sg = measured.get((st.logical_idx, st.stage, st.op_name))
        if sg is None:
            assert st.meas_tuples is None    # never flushed: renders "--"
            continue
        seen += 1
        assert st.meas_tuples == sg.n_tuples
        assert st.meas_batches == sg.n_batches
        assert st.meas_kv_bytes == sg.kv_bytes
        assert st.meas_batch == pytest.approx(sg.mean_batch)
        assert st.meas_cost_per_tuple_s == pytest.approx(
            sg.wall_s / max(sg.n_tuples, 1))
    assert seen == len(res.stage_stats)

    text = rep.render()
    assert "EXPLAIN ANALYZE" in text
    assert "meas/t" in text and "mbatch" in text
    assert "wall_s" in text and "runtime_s" in text
    # rows() carries the measured fields for programmatic use
    rows = [r for r in rep.rows() if "meas_tuples" in r]
    assert len(rows) == seen
    # the execution line reports the config that actually ran (the
    # per-call partition_size=16 override), not the session default
    assert rep.partition_size == 16
    assert "partition_size=16" in text


def test_explain_analyze_uses_the_executed_plan():
    """After new measured telemetry lands in the session store, a prior
    result's explain_analyze() must still render the plan that produced
    it — not today's (measured-fed, different) plan."""
    sess, items = _session_world()
    frame = (sess.frame(items).sem_filter("f", task_id=1)
             .with_guarantees(recall=0.7, precision=0.7))
    res = frame.execute(partition_size=16)
    planned0 = {(s.logical_idx, s.stage, s.op_name): s.exp_batch
                for s in res.raw.plan.stages}
    # recording bumps the store version; session.plan() would now re-plan
    sess.record_measured(res.raw)
    rep = res.explain_analyze()
    got = {(s.logical_idx, s.stage, s.op_name): s.exp_batch
           for s in rep.stages}
    assert got == planned0


def test_stream_wall_excludes_consumer_hold():
    """wall_s measures the engine, not the consumer's loop body: holding
    each partition must not inflate the run's elapsed time."""
    sess, items = _session_world()
    frame = (sess.frame(items).sem_filter("f", task_id=1)
             .with_guarantees(recall=0.7, precision=0.7))
    frame.plan()
    stream = frame.stream(partition_size=15, coalesce=1,
                          dispatcher="inline")
    held = 0.0
    for _ in stream:
        time.sleep(0.05)
        held += 0.05
    final = stream.result
    assert final.wall_s < held / 2       # ~0.2s of hold, ms of execution
    # per-partition windows exclude the hold too
    # (re-stream to inspect, holding between partitions)
    stream2 = frame.stream(partition_size=15, coalesce=1,
                           dispatcher="inline")
    parts = []
    for p in stream2:
        parts.append(p)
        time.sleep(0.05)
    assert sum(p.wall_s for p in parts) < 0.1


def test_stream_live_stats_track_progress():
    sess, items = _session_world()
    frame = (sess.frame(items).sem_filter("f", task_id=1)
             .with_guarantees(recall=0.7, precision=0.7))
    stream = frame.stream(partition_size=15, coalesce=1,
                          dispatcher="inline")
    assert stream.progress == 0.0 and stream.tuples_settled == 0
    first = next(stream)
    assert stream.tuples_settled == len(first)
    assert 0 < stream.progress < 1
    for _ in stream:
        pass
    assert stream.progress == 1.0
    final = stream.result
    live = _stats_by_key(stream.stage_stats)
    fin = _stats_by_key(final.stage_stats)
    assert set(live) == set(fin)
    for key in fin:
        assert live[key].n_tuples == fin[key].n_tuples
        assert live[key].n_batches == fin[key].n_batches


# ---------------------------------------------------------------------------
# MeasuredBatchStore: the measure -> plan loop
# ---------------------------------------------------------------------------

def _stats_row(op, wall_s, n_tuples, n_batches, kv=0):
    return {"op_name": op, "logical_idx": 0, "stage": 0, "wall_s": wall_s,
            "n_tuples": n_tuples, "n_llm_calls": n_tuples, "kv_bytes": kv,
            "n_batches": n_batches,
            "mean_batch": n_tuples / max(n_batches, 1)}


def test_measured_store_aggregates_and_versions():
    store = MeasuredBatchStore()
    assert len(store) == 0 and store.mean_batch("x") is None
    store.record_stats([_stats_row("a", 1.0, 40, 4),
                        _stats_row("b", 0.5, 10, 10)])
    store.record_stats([_stats_row("a", 1.0, 20, 2)])
    assert store.version == 2
    assert store.mean_batch("a") == pytest.approx(10.0)   # 60 tuples / 6
    assert store.wall_per_tuple("a") == pytest.approx(2.0 / 60)
    assert store.mean_batch("b") == pytest.approx(1.0)
    # tuple-weighted blend: op a dominates
    assert store.blended_width(["a", "b"]) == pytest.approx(70 / 16)
    assert store.blended_width(["missing"]) is None
    # an op shared by several pipelines must not be double-weighted
    assert store.blended_width(["a", "a", "b"]) == \
        store.blended_width(["a", "b"])
    # StageStats objects are accepted alongside dict rows
    store.record_stats([StageStats("c", 0, 0, wall_s=0.2, n_tuples=6,
                                   n_llm_calls=6, kv_bytes=3, n_batches=2)])
    assert store.mean_batch("c") == pytest.approx(3.0)
    # zero-batch rows are ignored (never flushed: nothing measured)
    store.record_stats([_stats_row("dead", 0.0, 0, 0)])
    assert "dead" not in store


def test_measured_store_loads_trajectory_snapshots(tmp_path):
    flat = [_stats_row("op-x", 2.0, 100, 5)]
    snap = {"meta": {"git_sha": "abc"},
            "stages": [_stats_row("op-x", 1.0, 60, 3),
                       _stats_row("op-y", 0.1, 8, 8)]}
    # the flat "latest" file duplicates the newest snapshot's rows —
    # from_dir must fold in only the timestamped snapshots, or the most
    # recent run would carry double weight in the trajectory
    (tmp_path / "stage_stats.json").write_text(json.dumps(flat))
    (tmp_path / "stage_stats-20260101T000000-abc.json").write_text(
        json.dumps(snap))
    (tmp_path / "stage_stats-20260102T000000-def.json").write_text(
        json.dumps(flat))
    (tmp_path / "stage_stats-broken.json").write_text("{not json")
    store = MeasuredBatchStore.from_dir(str(tmp_path))
    assert store.mean_batch("op-x") == pytest.approx(160 / 8)
    assert store.mean_batch("op-y") == pytest.approx(1.0)
    # the flat file can still be folded in explicitly
    extra = MeasuredBatchStore()
    extra.load_file(str(tmp_path / "stage_stats.json"))
    assert extra.mean_batch("op-x") == pytest.approx(20.0)
    out = tmp_path / "agg.json"
    store.save(str(out))
    assert json.loads(out.read_text())["op-x"]["n_tuples"] == 160


def test_batch_drift_ratio():
    _, plan, _ = _world()       # f-cheap planned at exp_batch 16
    stats = [StageStats("f-cheap", 0, 0, wall_s=0.1, n_tuples=32,
                        n_llm_calls=32, kv_bytes=0, n_batches=8)]
    # measured mean batch 4 vs planned 16 -> drift 4x either way
    assert batch_drift(plan, stats) == pytest.approx(4.0)
    stats[0].n_batches = 2      # measured 16 == planned: no drift
    assert batch_drift(plan, stats) == pytest.approx(1.0)
    # stages without a planned batch expectation are skipped
    assert batch_drift(plan, [StageStats("unknown", 9, 9, n_tuples=5,
                                         n_batches=5)]) == 1.0


def test_cold_profiling_price_leaves_out_layout_programs():
    """The planner prices an operator from its cold full-sample run less
    the flush's `layout_s`: the device programs that pad a cache batch
    compile per flat length, which follows the sample's item lengths and
    not the operator, so the first operator to meet a length must not pay
    for it. Whatever else the cold run spends stays in the price."""
    from repro.core import profile_query

    layout_s, other_s = 0.3, 0.05

    class _LayoutBackend(OracleBackend):
        def __init__(self, registry):
            super().__init__(registry)
            self.calls = 0

        def transfer_stats(self):
            return FlushCounters(layout_s=layout_s * self.calls)

        def score_filter(self, op, op_name, items):
            if op_name == "f-cheap":
                self.calls += 1
                time.sleep(layout_s)
            else:
                time.sleep(other_s)
            return super().score_filter(op, op_name, items)

    cheap, gold = _Filter("f-cheap", 1), _Filter("f-gold", 2, is_gold=True)
    backend = _LayoutBackend(lambda op: [cheap, gold])
    items = [_Item(i) for i in range(40)]
    q = Query([SemFilter("f", 1)], target_recall=0.8, target_precision=0.8)
    (prof,), sample = profile_query(q, items, backend, sample_frac=0.5)
    n = len(sample)
    assert prof.op_names == ["f-cheap", "f-gold"]
    assert prof.costs[0] < 0.2 * layout_s / n
    assert prof.costs[1] >= other_s / n


def test_plan_prices_measured_widths(tmp_path):
    """plan_query(measured=...) must price ops at their measured flush
    widths: a store claiming tiny real batches raises the amortized
    fixed cost and lowers exp_batch on the affected stages."""
    from repro.core import plan_query
    sess, items = _session_world()
    q = Query([SemFilter("f", 1)], target_recall=0.7, target_precision=0.7)
    base = plan_query(q, items, sess.backend, FASTCFG, sample_frac=0.5)
    store = MeasuredBatchStore()
    store.record_stats([_stats_row("f-cheap", 0.5, 30, 15),   # batch 2
                        _stats_row("f-gold", 0.5, 30, 15)])
    fed = plan_query(q, items, sess.backend, FASTCFG, sample_frac=0.5,
                     measured=store)
    by_op = {st.op_name: st for st in fed.stages}
    for name in ("f-cheap", "f-gold"):
        if name in by_op:
            assert by_op[name].exp_batch <= 2.0 + 1e-6
    base_ops = {st.op_name: st for st in base.stages}
    for name, st in by_op.items():
        if name in base_ops and base_ops[name].exp_batch > 2.0:
            assert st.exp_batch < base_ops[name].exp_batch


def test_session_replan_on_drift_feeds_measured_store():
    """Executing with flush batches far from the planned width must, with
    replan_on_drift set, record measured telemetry and re-plan against
    it — changing the BatchHint inputs (visible as shrunken exp_batch)."""
    sess, items = _session_world()
    frame = (sess.frame(items).sem_filter("f", task_id=1)
             .with_guarantees(recall=0.7, precision=0.7))
    plan0 = frame.plan()
    widths0 = {st.op_name: st.exp_batch for st in plan0.stages}
    assert len(sess.measured) == 0 and sess.n_replans == 0

    # coalesce=2 forces ~2-tuple flushes against a 64-wide planned batch
    res = frame.execute(partition_size=8, coalesce=2, replan_on_drift=4.0)
    assert sess.n_replans == 1
    assert len(sess.measured) > 0           # measured stats were recorded
    # the memoized plan now prices the measured (tiny) flush widths
    plan1 = frame.plan()
    assert plan1 is not plan0
    meas = {st.op_name: st.exp_batch for st in plan1.stages}
    for name, w in meas.items():
        mb = sess.measured.mean_batch(name)
        if mb is not None and widths0.get(name, 0) > 8:
            assert w < widths0[name]
            assert w <= mb + 1e-6
    # decisions are still a valid execution of the query
    assert res.accepted.shape == (len(items),)

    # a second execute at the planned widths should not re-trigger
    n = sess.n_replans
    frame.execute(partition_size=8, replan_on_drift=1e9)
    assert sess.n_replans == n
