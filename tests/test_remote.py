"""Remote engine members: wire protocol, config validation, loopback
parity, and degradation policies.

Four invariant families:

Protocol — frames round-trip (json floor, zlib past the compression
threshold, msgpack when both peers import it), version/magic mismatches
raise ProtocolError, clean EOF at a frame boundary is distinguishable
from a mid-frame truncation, semantic operators survive the wire with
their exact subclass, and the corpus hash is order-independent.

Validation — a remote EngineSpec is checked at construction: malformed
addresses, address + device / dispatcher affinity, unknown degradation
policies, and a remote gold engine all fail with a clear ValueError
before any socket is opened.

Parity — the load-bearing guarantee: a pool with one member served over
a 127.0.0.1 worker produces bit-identical decisions / map values /
per-engine StageStats to the all-local pool, for the SAME plan, across
inline and threads dispatchers, solo and through the concurrent
scheduler (where cross-query coalescing must also reduce wire calls).

Robustness — SIGKILL a real worker subprocess mid-run: under
on_unavailable="fallback" the run completes on the gold engine with
fallback counters > 0; under "fail" it raises RemoteEngineError without
poisoning the session (gold execution still works afterwards).
"""
import os
import signal
import socket
import threading

import numpy as np
import pytest

from repro.api import EngineSpec, Session, SessionConfig
from repro.core import PlannerConfig
from repro.core.logical import SemAgg, SemFilter, SemJoin, SemMap, SemTopK
from repro.data.synthetic import make_dataset
from repro.remote import (RemoteEngineError, RemoteEngineMember,
                          RemoteWorker, start_server)
from repro.remote import protocol as proto
from repro.remote.client import remote_members, remote_run_info
from repro.remote.testing import spawn_worker
from repro.runtime import gold_plan_for
from repro.scheduler import QueryScheduler

FAST = PlannerConfig(steps=120, restarts=2, snapshots=2)

# the worker's identity — the local "fast" spec and every worker in this
# module use exactly these values, which is what makes scores bit-equal
FAST_SPEC = dict(models=("sm",), sm_ratios=(0.8, 0.5), lg_ratios=())


# ---------------------------------------------------------------------------
# protocol units (no worker)
# ---------------------------------------------------------------------------

def test_frame_roundtrip_json_and_zlib():
    small = {"verb": "health", "n": 3, "xs": [1.5, -2.25]}
    frame = proto.encode_frame(small)
    msg, enc = proto.decode_frame(frame[:proto.HEADER.size],
                                  frame[proto.HEADER.size:])
    assert msg == small and enc == "json"
    # a frame past COMPRESS_MIN gets zlib'd and still round-trips
    big = {"verb": "sync", "items": [[i, list(range(40))]
                                     for i in range(300)]}
    frame = proto.encode_frame(big)
    flags = proto.HEADER.unpack(frame[:proto.HEADER.size])[2]
    assert flags & proto.FLAG_ZLIB
    assert len(frame) < len(str(big))
    msg, _ = proto.decode_frame(frame[:proto.HEADER.size],
                                frame[proto.HEADER.size:])
    assert msg == big


@pytest.mark.skipif(not proto.HAVE_MSGPACK, reason="msgpack not installed")
def test_frame_roundtrip_msgpack():
    obj = {"verb": "score_filter", "item_ids": list(range(64)),
           "scores": [0.125, -3.5]}
    frame = proto.encode_frame(obj, encoding="msgpack")
    flags = proto.HEADER.unpack(frame[:proto.HEADER.size])[2]
    assert flags & proto.FLAG_MSGPACK
    msg, enc = proto.decode_frame(frame[:proto.HEADER.size],
                                  frame[proto.HEADER.size:])
    assert msg == obj and enc == "msgpack"


def test_frame_rejects_bad_version_and_magic():
    payload = b"{}"
    bad_ver = proto.HEADER.pack(proto.MAGIC, proto.PROTOCOL_VERSION + 1,
                                0, len(payload))
    with pytest.raises(proto.ProtocolError, match="version"):
        proto.decode_frame(bad_ver, payload)
    bad_magic = proto.HEADER.pack(b"XX", proto.PROTOCOL_VERSION,
                                  0, len(payload))
    with pytest.raises(proto.ProtocolError, match="magic"):
        proto.decode_frame(bad_magic, payload)
    with pytest.raises(proto.ProtocolError, match="encoding"):
        proto.encode_frame({}, encoding="bson")


def test_send_recv_eof_vs_truncation():
    a, b = socket.socketpair()
    try:
        proto.send_msg(a, {"verb": "health"})
        msg, enc, nbytes = proto.recv_msg(b)
        assert msg == {"verb": "health"} and enc == "json" and nbytes > 0
        # clean EOF at a frame boundary: (None, "", 0), no exception
        a.close()
        assert proto.recv_msg(b) == (None, "", 0)
    finally:
        b.close()
    # a connection dropped MID-frame must raise, not read garbage
    a, b = socket.socketpair()
    try:
        frame = proto.encode_frame({"verb": "stats"})
        a.sendall(frame[:proto.HEADER.size + 1])
        a.close()
        with pytest.raises(proto.ProtocolError, match="mid-frame"):
            proto.recv_msg(b)
    finally:
        b.close()


def test_sem_codec_roundtrips_exact_subclass():
    ops = (SemFilter("f", 1), SemFilter("f", 1, modality="image"),
           SemMap("m", 2, out_column="v"),
           SemTopK("t", 3, k=5),
           SemAgg("a", 4, group_by="g", how="mode"),
           SemJoin("j", 5, on="col"))
    for op in ops:
        back = proto.sem_from_wire(proto.sem_to_wire(op))
        assert type(back) is type(op)
        assert back == op
    with pytest.raises(proto.ProtocolError):
        proto.sem_to_wire(object())
    with pytest.raises(proto.ProtocolError):
        proto.sem_from_wire({"kind": "reduce"})


def test_corpus_hash_order_independent_content_sensitive():
    pairs = [(1, (3, 4, 5)), (2, (6, 7)), (3, ())]
    h = proto.corpus_hash(pairs)
    assert proto.corpus_hash(reversed(pairs)) == h
    assert proto.corpus_hash([(1, (3, 4, 9)), (2, (6, 7)), (3, ())]) != h
    assert proto.corpus_hash([(1, (3, 4, 5)), (2, (6, 7))]) != h
    with pytest.raises(proto.ProtocolError, match="item_id"):
        proto.items_to_wire([{"not": "an item"}])


# ---------------------------------------------------------------------------
# config validation (satellite: remote specs are checked at construction)
# ---------------------------------------------------------------------------

def test_engine_spec_remote_validation():
    ok = EngineSpec("r", address="127.0.0.1:9410")
    assert ok.on_unavailable == "fallback"
    with pytest.raises(ValueError, match="host:port"):
        EngineSpec("r", address="no-port-here")
    with pytest.raises(ValueError, match="device"):
        EngineSpec("r", address="127.0.0.1:9410", device=0)
    with pytest.raises(ValueError, match="dispatcher"):
        EngineSpec("r", address="127.0.0.1:9410", dispatcher=2)
    with pytest.raises(ValueError, match="on_unavailable"):
        EngineSpec("r", address="127.0.0.1:9410", on_unavailable="retry")
    with pytest.raises(ValueError, match="timeout_s"):
        EngineSpec("r", address="127.0.0.1:9410", timeout_s=0.0)
    with pytest.raises(ValueError, match="remote_retries"):
        EngineSpec("r", address="127.0.0.1:9410", remote_retries=-1)


def test_remote_gold_engine_rejected():
    # a lone spec IS the gold engine — it cannot be remote
    with pytest.raises(ValueError, match="gold"):
        SessionConfig(engines=(EngineSpec("r", address="127.0.0.1:9410"),))
    with pytest.raises(ValueError, match="gold"):
        SessionConfig(
            engines=(EngineSpec("local"),
                     EngineSpec("r", address="127.0.0.1:9410")),
            gold_engine="r")
    # remote non-gold next to a local gold is the supported shape
    cfg = SessionConfig(
        engines=(EngineSpec("r", address="127.0.0.1:9410"),
                 EngineSpec("local")),
        gold_engine="local")
    assert cfg.resolved_engines()[0].address is not None


def test_member_constructor_validation():
    with pytest.raises(ValueError, match="host:port"):
        RemoteEngineMember("x", "nohost")
    with pytest.raises(ValueError, match="on_unavailable"):
        RemoteEngineMember("x", "127.0.0.1:9410", on_unavailable="punt")


# ---------------------------------------------------------------------------
# warm/evict no-op safety (satellite: never-built rungs must not crash)
# ---------------------------------------------------------------------------

def test_warm_evict_noop_on_unbuilt_rungs(tmp_path):
    worker = RemoteWorker("noop", cache_dir=str(tmp_path), **FAST_SPEC)
    eng = worker.engine
    # cold engine, nothing built: warm/evict are no-ops, not crashes
    assert eng.warm("sm", 0.5, [1, 2, 3]) == 0
    assert eng.warm("sm", 0.5, []) == 0
    assert eng.warm("unknown-model", 0.5, [1]) == 0
    assert eng.evict() == 0
    assert eng.evict("sm", 0.5) == 0
    # the wire verbs take the same path (item_ids None -> synced corpus,
    # which is empty before the first sync)
    assert worker.handle({"verb": "warm", "model": "sm", "ratio": 0.5}) \
        == {"ok": True, "batches": 0}
    assert worker.handle({"verb": "evict", "model": None, "ratio": None}) \
        == {"ok": True, "dropped": 0}
    # partially built rung: ids outside the built subset are skipped,
    # not KeyError'd; a never-built ratio stays a no-op
    items = make_dataset("warm", 12, seed=1).items
    eng.build_profiles("sm", items[:6], ratios=[0.5], prefill_batch=4)
    all_ids = [it.item_id for it in items]
    assert eng.warm("sm", 0.5, all_ids) >= 0
    assert eng.warm("sm", 0.8, all_ids) == 0
    assert eng.evict("sm", 0.8) == 0


# ---------------------------------------------------------------------------
# loopback world: one in-process worker + the local twin of its spec
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def world(tmp_path_factory):
    ds = make_dataset("remote", 90, seed=7)
    worker = RemoteWorker(
        "fast", cache_dir=str(tmp_path_factory.mktemp("worker")),
        **FAST_SPEC)
    server, _, addr = start_server(worker)
    yield ds, worker, addr
    server.shutdown()
    server.server_close()


def _accurate(tmp_path_factory, tag):
    return EngineSpec("accurate", models=("lg",),
                      sm_ratios=(), lg_ratios=(0.5,), include_cheap=False,
                      cache_dir=str(tmp_path_factory.mktemp(tag)))


def _session(tmp_path_factory, fast_spec, tag):
    return Session(SessionConfig(
        engines=(fast_spec, _accurate(tmp_path_factory, tag)),
        gold_engine="accurate",
        planner=FAST, sample_frac=0.35, partition_size=40))


@pytest.fixture(scope="module")
def sessions(world, tmp_path_factory):
    ds, _, addr = world
    local = _session(
        tmp_path_factory,
        EngineSpec("fast", cache_dir=str(tmp_path_factory.mktemp("fl")),
                   **FAST_SPEC),
        "al")
    remote = _session(tmp_path_factory,
                      EngineSpec("fast", address=addr), "ar")
    local.prepare(ds.items)
    remote.prepare(ds.items)
    yield ds, local, remote
    local.close()
    remote.close()


def _frame(sess, ds):
    return (sess.frame(ds.items)
            .sem_filter("f1", 1)
            .sem_map("extract v2", 2)
            .with_guarantees(recall=0.7, precision=0.7))


def test_session_builds_no_local_engine_for_remote_spec(sessions):
    ds, local, remote = sessions
    assert set(local.engines) == {"fast", "accurate"}
    assert set(remote.engines) == {"accurate"}          # no local slot
    members = remote_members(remote.backend)
    assert [m.engine_name for m in members] == ["fast"]
    with pytest.raises(ValueError, match="remote"):
        remote.backend_for(engine="fast")
    h = members[0].health()
    assert h["ok"] and h["n_items"] == len(ds.items)
    assert h["corpus_hash"] == members[0]._synced_hash


def test_catalog_matches_local_candidates(sessions):
    """The worker's catalog must reproduce the local engine's ladder —
    names, gold flag, and cost numbers — or pool ordering (and therefore
    planning) would diverge between the two sessions."""
    ds, local, remote = sessions
    for op in (SemFilter("f1", 1), SemMap("extract v2", 2)):
        lc = local.backend.candidates(op)
        rc = remote.backend.candidates(op)
        assert [c.name for c in rc] == [c.name for c in lc]
        assert [c.is_gold for c in rc] == [c.is_gold for c in lc]
        assert [c.cost_model() for c in rc] == [c.cost_model() for c in lc]
        assert [getattr(c, "engine_name", None) for c in rc] \
            == [getattr(c, "engine_name", None) for c in lc]


def test_every_fast_operator_scores_bit_identically(sessions):
    ds, local, remote = sessions
    op = SemFilter("f1", 1)
    batch = ds.items[:32]
    member, = remote_members(remote.backend)
    f0 = member.transfer_stats()
    for cand in local.backend.candidates(op):
        ls = local.backend.score_filter(op, cand.name, batch)
        rs = remote.backend.score_filter(op, cand.name, batch)
        np.testing.assert_array_equal(rs, ls)
        assert rs.dtype == np.float32
    mop = SemMap("extract v2", 2)
    for cand in local.backend.candidates(mop):
        lv, lcf = local.backend.run_map(mop, cand.name, batch)
        rv, rcf = remote.backend.run_map(mop, cand.name, batch)
        np.testing.assert_array_equal(rv, lv)
        np.testing.assert_array_equal(rcf, lcf)
    # the worker engine's flush counters cross the wire to this thread
    assert (member.transfer_stats() - f0).wait_s > 0


def test_staging_counters_cross_the_wire(world, sessions, monkeypatch):
    """The worker engine counts a staging reuse or allocation for every
    batch it loads from its store, and the counts reach the client's
    flush counters through the protocol."""
    ds, worker, _ = world
    _, local, remote = sessions
    member, = remote_members(remote.backend)
    store = worker.engine.store
    loads, inner = [], store.load_batch

    def load_batch(*a, **kw):
        loads.append(1)
        return inner(*a, **kw)

    monkeypatch.setattr(store, "load_batch", load_batch)
    worker.engine.evict()                      # every flush loads
    op = SemFilter("f1", 1)
    f0 = member.transfer_stats()
    for cand in local.backend.candidates(op):
        remote.backend.score_filter(op, cand.name, ds.items[:32])
    d = member.transfer_stats() - f0
    assert loads
    assert d.staged_reuses + d.staged_allocs == len(loads)


def test_lru_counters_cross_the_wire(world, sessions):
    """A batch the worker engine's device LRU already holds is served from
    it: the bytes it served reach the client's flush counters through the
    protocol, and nothing was evicted."""
    ds, worker, _ = world
    _, local, remote = sessions
    member, = remote_members(remote.backend)
    eng = worker.engine
    op = SemFilter("f1", 1)
    cand = next(c for c in local.backend.candidates(op)
                if c.name.startswith("fast/") and "-kv" in c.name)
    flag = eng.device_cache
    try:
        eng.device_cache = True
        eng.evict()
        remote.backend.score_filter(op, cand.name, ds.items[:16])
        f0 = member.transfer_stats()
        remote.backend.score_filter(op, cand.name, ds.items[:16])
        d = member.transfer_stats() - f0
    finally:
        eng.device_cache = flag
        eng.evict()
    assert d.lru_hit_bytes > 0 and d.padded_bytes == 0
    assert d.lru_evictions == 0


@pytest.mark.parametrize("dispatcher", ["inline", "threads:2"])
def test_same_plan_parity_local_vs_remote(sessions, dispatcher):
    """THE parity pin: one plan, two pools (one wired through the
    loopback worker) — decisions, map values, and per-engine StageStats
    must be bit-identical, and the remote run's wire telemetry must
    show real calls with zero fallbacks."""
    ds, local, remote = sessions
    query = _frame(local, ds).to_query()
    plan = local.plan(query, ds.items)
    engines = {st.engine for st in plan.stages}
    assert engines == {"fast", "accurate"}   # else the test is vacuous
    lr = local.run(plan, query, ds.items, dispatcher=dispatcher)
    rr = remote.run(plan, query, ds.items, dispatcher=dispatcher)
    np.testing.assert_array_equal(rr.accepted, lr.accepted)
    assert set(rr.map_values) == set(lr.map_values)
    for li in lr.map_values:
        np.testing.assert_array_equal(rr.map_values[li], lr.map_values[li])
    key = lambda sg: (sg.logical_idx, sg.stage, sg.op_name)
    mine = {key(sg): sg for sg in rr.stage_stats}
    ref = {key(sg): sg for sg in lr.stage_stats}
    assert set(mine) == set(ref)
    for k, sg in mine.items():
        assert sg.engine == ref[k].engine
        assert sg.n_tuples == ref[k].n_tuples
        assert sg.n_llm_calls == ref[k].n_llm_calls
        assert sg.n_batches == ref[k].n_batches
        # per-engine KV telemetry survives the wire exactly
        assert sg.kv_bytes == ref[k].kv_bytes
    assert lr.remote is None                 # all-local run: no footer
    assert rr.remote is not None
    assert rr.remote["calls"] > 0
    assert rr.remote["fallbacks"] == 0 and rr.remote["errors"] == 0
    assert set(rr.remote["engines"]) == {"fast"}
    assert rr.remote["rtt_ms_p95"] >= rr.remote["rtt_ms_p50"] >= 0.0


def test_remote_plans_identically_and_explains_wire_footer(sessions):
    """Planning THROUGH the remote catalog (costs from the wire,
    profiling scores over the wire) lands on the same plan as the
    all-local session, and EXPLAIN ANALYZE grows the remote footer."""
    ds, local, remote = sessions
    local_plan = _frame(local, ds).plan()
    res = _frame(remote, ds).execute(dispatcher="inline")
    rplan = res.explain_analyze()
    assert [st.op_name for st in local_plan.stages] \
        == [s.op_name for s in rplan.stages]
    text = rplan.render()
    assert "remote:" in text and "calls=" in text and "rtt_ms" in text
    assert "remote fast:" in text
    # the all-local session never grows the footer
    ltext = _frame(local, ds).execute(dispatcher="inline") \
        .explain_analyze().render()
    assert "remote:" not in ltext


def test_scheduler_coalesces_remote_wire_calls(sessions):
    """K concurrent copies through the QueryScheduler: decisions stay
    bit-identical to solo, and cross-query flush merging reaches the
    wire — fewer remote calls than K solo runs would issue."""
    ds, _, remote = sessions
    member = remote_members(remote.backend)[0]
    frame = _frame(remote, ds)
    before = member.snapshot()
    solo = frame.execute(dispatcher="inline")
    solo_calls = member.snapshot()["calls"] - before["calls"]
    assert solo_calls > 0                    # fast stages really remote
    frame.plan()
    K = 3
    before = member.snapshot()
    with QueryScheduler(remote, max_concurrent=K, paused=True) as sched:
        handles = [sched.submit(frame) for _ in range(K)]
        sched.resume()
        results = [h.result(timeout=300) for h in handles]
        stats = sched.stats()
    sched_calls = member.snapshot()["calls"] - before["calls"]
    for r in results:
        np.testing.assert_array_equal(r.accepted, solo.accepted)
        for li in solo.map_values:
            np.testing.assert_array_equal(r.map_values[li],
                                          solo.map_values[li])
    assert stats["n_merged_calls"] >= 1
    # the hub's merged groups became single wire calls
    assert sched_calls < K * solo_calls


def test_remote_run_info_snapshot_math():
    a = {"engine": "e", "calls": 2, "retries": 0, "fallbacks": 0,
         "errors": 0, "bytes_sent": 1024, "bytes_recv": 1024,
         "rtt_count": 2, "rtt_total_s": 0.004, "rtt_recent": [0.001, 0.003]}
    assert remote_run_info({"e": a}, {"e": dict(a)}) is None  # no delta
    b = dict(a, calls=5, rtt_count=5, bytes_recv=3072,
             rtt_recent=[0.001, 0.003, 0.002, 0.002, 0.010])
    info = remote_run_info({"e": a}, {"e": b})
    assert info["calls"] == 3 and info["engines"]["e"]["calls"] == 3
    assert info["wire_kb"] == pytest.approx(2.0)
    assert info["rtt_ms_p50"] == pytest.approx(2.0)
    assert info["rtt_ms_p95"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# robustness: a real worker subprocess, SIGKILLed mid-run
# ---------------------------------------------------------------------------

def test_worker_crash_fallback_and_fail_policies(tmp_path_factory):
    ds = make_dataset("remote", 90, seed=7)
    proc, addr = spawn_worker(name="fast", **FAST_SPEC)
    fb_sess = _session(
        tmp_path_factory,
        EngineSpec("fast", address=addr, remote_retries=1,
                   on_unavailable="fallback"), "fb")
    fail_sess = _session(
        tmp_path_factory,
        EngineSpec("fast", address=addr, remote_retries=0,
                   on_unavailable="fail"), "ff")
    try:
        query = _frame(fb_sess, ds).to_query()
        # plan (and thereby fetch + memoize the catalog) while alive;
        # the second session's sync is an idempotent hash check
        fb_plan = fb_sess.plan(query, ds.items)
        fail_plan = fail_sess.plan(query, ds.items)
        assert {st.engine for st in fb_plan.stages} \
            == {"fast", "accurate"}

        # --- fallback: SIGKILL between partitions of a streaming run ---
        # coalesce=1 keeps flushes per-partition (the default threshold
        # would buffer the whole run's remote work into the first
        # partition's settle, leaving nothing to fail after the kill)
        member = remote_members(fb_sess.backend)[0]
        gen = fb_sess.iter_run(fb_plan, query, ds.items, partition_size=30,
                               coalesce=1, dispatcher="inline")
        next(gen)                            # partition 1 over the wire
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        result = None
        try:
            while True:
                next(gen)
        except StopIteration as stop:
            result = stop.value
        assert result is not None
        assert result.accepted.shape == (len(ds.items),)
        snap = member.snapshot()
        assert snap["fallbacks"] > 0         # flushes re-routed to gold
        assert snap["retries"] > 0           # transport retries happened
        # degraded decisions remain exact where the fallback IS gold:
        # every fallback flush scored with the gold operator, so the
        # result set is still a valid decision vector over the corpus
        assert result.accepted.dtype == bool

        # --- fail: same dead worker, policy raises, session survives ---
        with pytest.raises(RemoteEngineError) as ei:
            fail_sess.run(fail_plan, query, ds.items, dispatcher="inline")
        assert ei.value.transport and ei.value.engine == "fast"
        # the session is not poisoned: gold execution (local engines
        # only) still completes for the same query
        gold = fail_sess.gold(query, ds.items)
        assert gold.accepted.shape == (len(ds.items),)
        gp = gold_plan_for(query, fail_sess.backend)
        again = fail_sess.run(gp, query, ds.items, dispatcher="inline")
        assert again.accepted.shape == (len(ds.items),)
        assert again.remote is None          # gold plan: no wire calls
    finally:
        proc.kill()
        fb_sess.close()
        fail_sess.close()


def test_application_errors_are_never_masked_by_fallback(world):
    """A worker-reported error (unknown operator) is a misconfiguration,
    not an outage — it must raise even under on_unavailable='fallback'."""
    ds, _, addr = world
    member = RemoteEngineMember("fast", addr, on_unavailable="fallback")
    try:
        member.sync(ds.items)
        op = SemFilter("f1", 1)
        with pytest.raises(RemoteEngineError) as ei:
            member._wire_filter(op, "no-such-op", ds.items[:4])
        assert not ei.value.transport
        assert "no-such-op" in str(ei.value)
    finally:
        member.close()


def test_circuit_breaker_opens_and_fails_fast(world):
    """After breaker_threshold consecutive transport failures the
    breaker fails fast (no connect attempt) until the reset window."""
    # a port with nothing behind it: reserve then release
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead = f"127.0.0.1:{probe.getsockname()[1]}"
    probe.close()
    member = RemoteEngineMember("gone", dead, retries=0, backoff_s=0.0,
                                breaker_threshold=2, breaker_reset_s=60.0,
                                on_unavailable="fail")
    for _ in range(2):
        with pytest.raises(RemoteEngineError, match="unreachable"):
            member.health()
    with pytest.raises(RemoteEngineError, match="circuit open"):
        member.health()
    assert member.snapshot()["errors"] == 2  # breaker trips count no call
