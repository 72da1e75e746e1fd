"""Serving fast path: fused one-dispatch flushes, device-resident profile
cache, int8 KV profiles end to end, memory-bounded bucketing, and the
API-level guarantee that flipping STRETTO_KERNELS between the ref oracle
and Pallas interpret mode changes neither query decisions nor the
EXPLAIN ANALYZE telemetry."""
import numpy as np
import pytest

from repro.api import Session, SessionConfig
from repro.cache.store import CacheStore, Profile
from repro.core import PlannerConfig
from repro.obs import FlushCounters
from repro.data.synthetic import (TOK_NO, TOK_YES, filter_query_token,
                                  make_dataset, make_planted_params,
                                  planted_config)
from repro.serving.engine import KERNEL_BLOCK_S, ServingEngine, _bucket
from repro.serving.operators import KVCacheLLMOperator


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    ds = make_dataset("fp", 60, seed=3)
    store = CacheStore(str(tmp_path_factory.mktemp("cache")))
    eng = ServingEngine(store, device_cache=False)
    cfg = planted_config("sm")
    eng.register_model("sm", cfg, make_planted_params(cfg, seed=1))
    eng.build_profiles("sm", ds.items, ratios=[0.0, 0.5],
                       quant_ratios=[0.5], prefill_batch=30)
    return eng, ds


def _ids(ds, n):
    return [it.item_id for it in ds.items[:n]]


# ---------------------------------------------------------------------------
# bucketing respects the memory budget
# ---------------------------------------------------------------------------

def test_bucket_cap_never_exceeds_memory_batch(engine, monkeypatch):
    """Regression: power-of-two bucketing used to round 48 ids up to a
    64-wide batch even when the memory budget only admitted 48."""
    eng, ds = engine
    per_item = eng.store.item_nbytes(Profile("sm", 0.0))
    widths = []
    orig = eng.store.load_batch

    def spy(cfg, profile, item_ids, **kw):
        widths.append(len(item_ids))
        return orig(cfg, profile, item_ids, **kw)

    monkeypatch.setattr(eng.store, "load_batch", spy)
    budget0 = eng.memory_budget
    try:
        eng.memory_budget = 48 * per_item          # admits 48, not 64
        assert eng.max_batch_for("sm", 0.0) == 48
        ids = _ids(ds, 48)
        out = eng.run_filter("sm", 0.0, ids, [filter_query_token(1)],
                             TOK_YES, TOK_NO)
        assert len(out) == 48
        assert widths == [48]                      # not bucketed to 64
        # a ragged final chunk still buckets up (shape-diversity bound)
        eng.memory_budget = 20 * per_item
        widths.clear()
        eng.run_filter("sm", 0.0, _ids(ds, 45), [filter_query_token(1)],
                       TOK_YES, TOK_NO)
        assert widths == [20, 20, 8]   # _bucket(5) = 8, under the cap
    finally:
        eng.memory_budget = budget0


def test_bucket_helper():
    assert [_bucket(n) for n in (1, 2, 3, 5, 48, 64)] == [1, 2, 4, 8, 64, 64]


# ---------------------------------------------------------------------------
# batch sizing reads store metadata, not shards
# ---------------------------------------------------------------------------

def test_max_batch_for_reads_metadata_not_shards(engine, monkeypatch):
    """max_batch_for runs on every flush; it must not decompress an .npz
    shard. A store reopened on the same root (cold in-memory cache) must
    size batches from _meta.jsonl alone."""
    eng, _ = engine
    store2 = CacheStore(eng.store.root)

    def boom(*a, **k):
        raise AssertionError("max_batch_for read a shard")

    monkeypatch.setattr(store2, "load", boom)
    eng2 = ServingEngine(store2, memory_budget_bytes=eng.memory_budget)
    for ratio, quant in ((0.0, False), (0.5, False), (0.5, True)):
        b = eng2.max_batch_for("sm", ratio, quant=quant)
        assert 1 <= b <= eng2.max_batch
    # int8 shards are smaller -> at least as many fit in the budget
    per = store2.item_nbytes(Profile("sm", 0.5))
    eng2.memory_budget = 10 * per
    assert (eng2.max_batch_for("sm", 0.5, quant=True)
            >= eng2.max_batch_for("sm", 0.5))


# ---------------------------------------------------------------------------
# fused flush: one attention dispatch per flush
# ---------------------------------------------------------------------------

def test_fused_one_dispatch_per_flush(engine):
    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(1)]
    assert eng.fused   # default on
    base = eng.attn_dispatches
    fused = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
    assert eng.attn_dispatches - base == 1         # ONE fused dispatch
    try:
        eng.fused = False
        eng._decode_jit.clear()
        base = eng.attn_dispatches
        scan = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        assert eng.attn_dispatches - base == len(query)  # one per token
    finally:
        eng.fused = True
        eng._decode_jit.clear()
    # and the fused path computes the same answer as the scan
    np.testing.assert_allclose(fused, scan, atol=1e-4)


def test_fused_multi_token_query(engine):
    """Multi-token operator queries (the common case) still flush once."""
    eng, ds = engine
    ids = _ids(ds, 6)
    query = [filter_query_token(1), filter_query_token(2),
             filter_query_token(3)]
    base = eng.attn_dispatches
    fused = eng.run_filter("sm", 0.5, ids, query, TOK_YES, TOK_NO)
    assert eng.attn_dispatches - base == 1
    try:
        eng.fused = False
        eng._decode_jit.clear()
        base = eng.attn_dispatches
        scan = eng.run_filter("sm", 0.5, ids, query, TOK_YES, TOK_NO)
        assert eng.attn_dispatches - base == len(query)
    finally:
        eng.fused = True
        eng._decode_jit.clear()
    np.testing.assert_allclose(fused, scan, atol=1e-4)


# ---------------------------------------------------------------------------
# device-resident profile cache
# ---------------------------------------------------------------------------

def test_device_cache_hit_skips_load_and_kv_bytes(engine):
    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(2)]
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        h0, m0 = eng.dev_cache_hits, eng.dev_cache_misses
        first = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        assert eng.dev_cache_misses - m0 == 1
        bytes_after_first = eng.store.bytes_loaded
        again = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        # hit: identical results, no reload, kv_bytes unchanged
        np.testing.assert_array_equal(first, again)
        assert eng.dev_cache_hits - h0 == 1
        assert eng.store.bytes_loaded == bytes_after_first
        # a different batch is a miss and DOES count bytes
        other = _ids(ds, 10)[8:]
        eng.run_filter("sm", 0.0, other, query, TOK_YES, TOK_NO)
        assert eng.store.bytes_loaded > bytes_after_first
        assert eng.dev_cache_misses - m0 == 2
    finally:
        eng.device_cache = False
        eng.device_cache_clear()


def test_device_cache_keyed_by_device(engine):
    """A batch cached for one placement is never handed to a flush
    placed on another device: the device is part of the LRU key."""
    import jax
    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(2)]
    dev = jax.devices()[-1]
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        m0 = eng.dev_cache_misses
        unplaced = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        with eng.place_on(dev):
            placed = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
            again = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        assert eng.dev_cache_misses - m0 == 2        # one per placement
        assert {k[-1] for k in eng._dev_cache} == {None, dev.id}
        for key, (cache, _) in eng._dev_cache.items():
            if key[-1] == dev.id:
                assert cache["k"].devices() == {dev}
        np.testing.assert_array_equal(unplaced, placed)
        np.testing.assert_array_equal(placed, again)
    finally:
        eng.device_cache = False
        eng.device_cache_clear()


def test_lower_flush_follows_kernels_backend(engine):
    """lower_flush lowers the program a flush runs, with the engine's
    kernels backend: the Pallas (interpret) and ref programs differ."""
    eng, ds = engine
    ids = _ids(ds, 5)
    query = [filter_query_token(1)]
    kernels0 = eng.kernels
    try:
        texts = {}
        for backend in ("ref", "interpret"):
            eng.kernels = backend
            texts[backend] = eng.lower_flush("sm", 0.5, ids, query).as_text()
        assert texts["ref"] != texts["interpret"]
        assert "tpu_custom_call" not in texts["ref"]
    finally:
        eng.kernels = kernels0


def test_device_cache_disabled_always_loads(engine):
    eng, ds = engine
    assert not eng.device_cache        # suite default (conftest)
    ids = _ids(ds, 4)
    query = [filter_query_token(3)]
    eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
    b0 = eng.store.bytes_loaded
    eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
    assert eng.store.bytes_loaded > b0   # every flush loads, and counts


def test_device_cache_evicts_lru_under_budget(engine):
    eng, ds = engine
    per_item = eng.store.item_nbytes(Profile("sm", 0.0))
    budget0 = eng.memory_budget
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        # room for ~2 four-item padded batches, not 6
        eng.memory_budget = 16 * per_item
        query = [filter_query_token(1)]
        for s in range(6):
            ids = _ids(ds, 24)[4 * s:4 * s + 4]
            eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        assert len(eng._dev_cache) < 6
        assert eng._dev_bytes <= eng.memory_budget \
            or len(eng._dev_cache) == 1
    finally:
        eng.device_cache = False
        eng.device_cache_clear()
        eng.memory_budget = budget0


def test_lru_hit_bytes_count_a_repeated_batch(engine):
    """A repeated batch is served from the device LRU and counts, in
    `lru_hit_bytes`, the bytes of the padded batch its first flush loaded
    and stored (`padded_bytes` of that miss); nothing is evicted."""
    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(2)]
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        c0 = eng.transfer_stats_local()
        eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        miss = eng.transfer_stats_local() - c0
        (stored, _), = eng._dev_cache.values()
        eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        hit = eng.transfer_stats_local() - c0 - miss
    finally:
        eng.device_cache = False
        eng.device_cache_clear()
    assert miss.lru_hit_bytes == 0 and miss.padded_bytes > 0
    assert hit.lru_hit_bytes == miss.padded_bytes == sum(
        int(v.nbytes) for k, v in stored.items() if k != "lengths")
    assert hit.padded_bytes == 0
    assert miss.lru_evictions == hit.lru_evictions == 0


def test_lru_evictions_counted_under_a_small_budget(engine):
    """With a budget that holds one batch and not two, every load after
    the first pushes the oldest entry out and counts it."""
    eng, ds = engine
    query = [filter_query_token(1)]
    batches = [_ids(ds, 12)[4 * s:4 * s + 4] for s in range(3)]
    budget0 = eng.memory_budget
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        eng.run_filter("sm", 0.0, batches[0], query, TOK_YES, TOK_NO)
        eng.memory_budget = 1.5 * eng._dev_bytes
        eng.device_cache_clear()
        c0 = eng.transfer_stats_local()
        for ids in batches:
            eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        d = eng.transfer_stats_local() - c0
        assert len(eng._dev_cache) == 1
    finally:
        eng.device_cache = False
        eng.device_cache_clear()
        eng.memory_budget = budget0
    assert d.lru_evictions == 2
    assert d.lru_hit_bytes == 0


def test_lru_lookup_span_says_hit_or_miss(engine, tmp_path):
    """Each LRU lookup opens `stretto.engine.lookup` inside
    `stretto.engine.load`, with `hit` 0 for a miss and 1 for a hit."""
    import glob

    import jax
    from jax.profiler import ProfileData

    eng, ds = engine
    ids = _ids(ds, 4)
    try:
        eng.device_cache = True
        eng.device_cache_clear()
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(2):
                eng.run_filter("sm", 0.0, ids, [filter_query_token(3)],
                               TOK_YES, TOK_NO)
    finally:
        eng.device_cache = False
        eng.device_cache_clear()
    spans = {"stretto.engine.load": [], "stretto.engine.lookup": []}
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append(
                        (line.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    lookups = sorted(spans["stretto.engine.lookup"], key=lambda e: e[1])
    assert [int(e[3]["hit"]) for e in lookups] == [0, 1]
    for ln, s, e, _ in lookups:
        assert any(ln == la and sa <= s and e <= ea
                   for la, sa, ea, _ in spans["stretto.engine.load"])


# ---------------------------------------------------------------------------
# int8 KV profiles end to end
# ---------------------------------------------------------------------------

def test_int8_profile_stored_and_distinct(engine):
    eng, ds = engine
    p8 = Profile("sm", 0.5, quant=True)
    assert p8.tag.endswith("__q8")
    shard = eng.store.load(p8, ds.items[0].item_id)
    assert shard["k"].dtype == np.int8 and shard["v"].dtype == np.int8
    assert shard["k_scale"].dtype == np.float32
    assert shard["k_scale"].shape == shard["k"].shape[:-1]
    # int8 shards are materially smaller than their f32 rung
    assert (eng.store.item_nbytes(p8)
            < 0.6 * eng.store.item_nbytes(Profile("sm", 0.5)))


def test_int8_filter_accuracy(engine):
    """int8 decisions track the f32 rung: the quantization is a real
    precision trade, not a different answer."""
    eng, ds = engine
    ids = [it.item_id for it in ds.items]
    q = [filter_query_token(1)]
    lo_f32 = eng.run_filter("sm", 0.5, ids, q, TOK_YES, TOK_NO)
    lo_int8 = eng.run_filter("sm", 0.5, ids, q, TOK_YES, TOK_NO, quant=True)
    agree = ((lo_f32 > 0) == (lo_int8 > 0)).mean()
    assert agree > 0.9
    np.testing.assert_allclose(lo_int8, lo_f32, atol=0.5)


def test_int8_operator_surface(engine):
    eng, _ = engine
    op32 = KVCacheLLMOperator(eng, "sm", 0.5)
    op8 = KVCacheLLMOperator(eng, "sm", 0.5, quant=True)
    assert op8.name != op32.name and "i8" in op8.name
    assert op8.cost_model() < op32.cost_model()
    assert op8.max_batch() >= 1


# ---------------------------------------------------------------------------
# API level: backend flip changes nothing observable
# ---------------------------------------------------------------------------

FAST = PlannerConfig(steps=120, restarts=2, snapshots=2)


@pytest.fixture(scope="module")
def api_world(tmp_path_factory):
    ds = make_dataset("fpapi", 40, seed=9)
    session = Session(SessionConfig(
        cache_dir=str(tmp_path_factory.mktemp("cache")),
        models=("sm",), profile_ratios=(0.0, 0.8),
        sm_ratios=(0.8, 0.0), lg_ratios=(0.0,),
        planner=FAST, sample_frac=0.4, partition_size=20))
    session.prepare(ds.items)
    yield ds, session
    session.close()


def _stats_key(result):
    return [(s.op_name, s.n_tuples, s.n_llm_calls, s.kv_bytes, s.n_batches)
            for s in result.stage_stats]


def test_decisions_identical_across_kernel_backends(api_world, monkeypatch):
    """STRETTO_KERNELS=ref vs interpret: same accepted set, same map
    values, same EXPLAIN ANALYZE counters — on both dispatchers. The
    backend is resolved at flush time, so flipping the env between runs
    of one session exercises real re-dispatch."""
    ds, sess = api_world
    frame = sess.frame(ds.items).sem_filter("f1", 1).sem_map("m2", 2)
    runs = {}
    for backend in ("ref", "interpret"):
        monkeypatch.setenv("STRETTO_KERNELS", backend)
        for eng in sess.engines.values():
            eng._decode_jit.clear()
        for dispatcher in ("inline", "threads"):
            runs[(backend, dispatcher)] = frame.execute(
                dispatcher=dispatcher)
    monkeypatch.delenv("STRETTO_KERNELS", raising=False)
    base = runs[("ref", "inline")]
    for key, res in runs.items():
        np.testing.assert_array_equal(res.accepted, base.accepted,
                                      err_msg=str(key))
        for col, vals in res.map_values.items():
            np.testing.assert_array_equal(vals, base.map_values[col],
                                          err_msg=str(key))
        assert _stats_key(res) == _stats_key(base), key
    # EXPLAIN ANALYZE is identical apart from measured wall-clock columns
    # and the flush line's host times, compiles and bytes (which follow
    # the compile cache and the device LRU, not the kernels backend)
    import re

    def strip_times(text):
        text = re.sub(r"\d+\.\d+(ms|s|us)\b", "<t>", text)
        text = re.sub(r"^flush: .*$", "flush: <measured>", text,
                      flags=re.M)
        return re.sub(r"(runtime_s|wall_s)=\d+\.\d+", r"\1=<t>", text)

    rep_ref = strip_times(runs[("ref", "inline")].explain_analyze().render())
    rep_int = strip_times(
        runs[("interpret", "inline")].explain_analyze().render())
    assert rep_ref == rep_int


def test_explain_analyze_prints_staging_counters(api_world):
    """EXPLAIN ANALYZE's flush line carries the staging counters summed
    over the run's stage flushes: one per batch the engines loaded."""
    import re

    ds, sess = api_world
    for eng in sess.engines.values():
        eng.device_cache_clear()
    res = sess.frame(ds.items).sem_filter("f1", 1).execute()
    flush = [s.flush for s in res.stage_stats]
    line, = re.findall(r"^flush: .*$", res.explain_analyze().render(),
                       flags=re.M)
    reuses = sum(f.staged_reuses for f in flush)
    allocs = sum(f.staged_allocs for f in flush)
    assert reuses + allocs > 0
    assert line.endswith(f" staged_reuses={reuses} staged_allocs={allocs}")


def test_explain_analyze_prints_h2d_bytes(api_world, monkeypatch):
    """EXPLAIN ANALYZE's flush line carries `h2d_MB`: the bytes that
    crossed to the device, summed over the run's stage flushes. They are
    exactly the flat buffers each load sent for its layout on the device,
    their item spans and the lengths vectors, under the padded batches'
    bytes."""
    import re

    from repro.cache import store as store_mod

    ds, sess = api_world
    flats, spans_sent, lengths = [], {}, []
    unflatten = store_mod._unflatten

    def spy(flat, spans, smax):
        flats.append(flat.nbytes)
        spans_sent[id(spans)] = spans      # held, so ids stay unique
        return unflatten(flat, spans, smax)

    frame = sess.frame(ds.items).sem_filter("f1", 1)
    frame.execute()                        # plans: its profiling loads
    monkeypatch.setattr(store_mod, "_unflatten", spy)
    for eng in sess.engines.values():
        eng.device_cache_clear()
        load_batch = eng.store.load_batch

        def load_spy(*a, _inner=load_batch, **kw):
            cache, lens = _inner(*a, **kw)
            lengths.append(lens.nbytes)
            return cache, lens

        monkeypatch.setattr(eng.store, "load_batch", load_spy)
    res = frame.execute()
    flush = [s.flush for s in res.stage_stats]
    h2d = sum(f.h2d_bytes for f in flush)
    assert flats and h2d < sum(f.padded_bytes for f in flush)
    assert h2d == (sum(flats) + sum(a.nbytes for a in spans_sent.values())
                   + sum(lengths))
    line, = re.findall(r"^flush: .*$", res.explain_analyze().render(),
                       flags=re.M)
    assert f" h2d_MB={h2d / 1e6:.1f} " in line


def test_explain_analyze_prints_lru_counters(api_world):
    """EXPLAIN ANALYZE's flush line carries the device-LRU counters summed
    over the run's stage flushes: a second run of the same query is
    served from the LRU and evicts nothing."""
    import re

    ds, sess = api_world
    engines = list(sess.engines.values())
    flags = [eng.device_cache for eng in engines]
    try:
        for eng in engines:
            eng.device_cache = True
            eng.device_cache_clear()
        frame = sess.frame(ds.items).sem_filter("f1", 1)
        frame.execute()
        res = frame.execute()
    finally:
        for eng, flag in zip(engines, flags):
            eng.device_cache = flag
            eng.device_cache_clear()
    flush = [s.flush for s in res.stage_stats]
    hit_bytes = sum(f.lru_hit_bytes for f in flush)
    assert hit_bytes > 0 and sum(f.padded_bytes for f in flush) == 0
    line, = re.findall(r"^flush: .*$", res.explain_analyze().render(),
                       flags=re.M)
    assert f" lru_hit_MB={hit_bytes / 1e6:.1f} lru_evictions=0 " in line


def test_session_config_validates_kernels_backend():
    """The kernels knob is part of the declarative config surface and is
    validated at construction, not first flush."""
    from repro.api import EngineSpec
    spec = EngineSpec("e", kernels="ref", fused=False, device_cache=True)
    assert spec.kernels == "ref"
    with pytest.raises(ValueError, match="kernels"):
        EngineSpec("e", kernels="cuda")
    cfg = SessionConfig(kernels="interpret", cache_dir="/tmp/nowhere")
    assert cfg.resolved_engines()[0].kernels == "interpret"


# ---------------------------------------------------------------------------
# async H2D overlap + donation
# ---------------------------------------------------------------------------

def test_async_h2d_identical_results_and_prefetch_s(engine):
    """Transfer overlap is a schedule change, not a math change: with the
    memory budget forcing a multi-batch flush, async_h2d=True must return
    bit-identical logits while the calling thread's prefetch_s (host time
    spent loading the next batch before the current result was forced)
    and donated_bytes (consumed KV buffers handed back to XLA) counters
    both advance."""
    eng, ds = engine
    ids = _ids(ds, 24)
    query = [filter_query_token(1)]
    per_item = eng.store.item_nbytes(Profile("sm", 0.0))
    budget0, flag0 = eng.memory_budget, eng.async_h2d
    assert eng.async_h2d                           # overlap is on by default
    try:
        eng.memory_budget = 8 * per_item           # forces 3 flush batches
        assert eng.max_batch_for("sm", 0.0) == 8
        eng.async_h2d = False
        base = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        eng.async_h2d = True
        c0 = eng.transfer_stats_local()
        overlapped = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        d = eng.transfer_stats_local() - c0
        np.testing.assert_array_equal(overlapped, base)
        assert d.prefetch_s > 0                    # prefetches were timed
        assert d.donated_bytes > 0                 # consumed KV donated
    finally:
        eng.async_h2d = flag0
        eng.memory_budget = budget0


def test_async_h2d_single_batch_no_prefetch_s(engine):
    """A corpus that fits one flush batch has no 'next cohort' to stage:
    prefetch_s must not move (nothing was prefetched), while donation
    still returns the one consumed cache buffer."""
    eng, ds = engine
    ids = _ids(ds, 6)
    query = [filter_query_token(2)]
    flag0 = eng.async_h2d
    try:
        eng.async_h2d = True
        c0 = eng.transfer_stats_local()
        eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        d = eng.transfer_stats_local() - c0
        assert d.prefetch_s == 0
        assert d.donated_bytes > 0
    finally:
        eng.async_h2d = flag0


def test_donation_disabled_with_device_cache(engine):
    """The device LRU keeps references to cached KV buffers, so donating
    them would hand XLA memory the cache later reuses — donation must be
    gated off whenever device_cache is on, and cache hits must still
    return identical results under async_h2d."""
    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(3)]
    flag0 = eng.async_h2d
    try:
        eng.async_h2d = True
        eng.device_cache = True
        eng.device_cache_clear()
        d0 = eng.transfer_stats_local().donated_bytes
        first = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        again = eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        np.testing.assert_array_equal(first, again)
        assert eng.transfer_stats_local().donated_bytes == d0  # never
    finally:
        eng.async_h2d = flag0
        eng.device_cache = False
        eng.device_cache_clear()


def test_transfer_stats_thread_local(engine):
    """The executor attributes flush-counter deltas to the flush that
    caused them via thread-scoped counters (a flush runs entirely on one
    dispatcher thread) — an async run must advance the calling thread's
    transfer_stats_local, and a fresh thread must start at zero."""
    import threading

    eng, ds = engine
    ids = _ids(ds, 8)
    query = [filter_query_token(1)]
    flag0 = eng.async_h2d
    try:
        eng.async_h2d = True
        t0 = eng.transfer_stats_local()
        eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
        t1 = eng.transfer_stats_local()
        assert t1.donated_bytes > t0.donated_bytes  # this thread donated
        seen = {}
        th = threading.Thread(
            target=lambda: seen.update(other=eng.transfer_stats_local()))
        th.start()
        th.join()
        assert seen["other"] == FlushCounters()    # not leaked cross-thread
    finally:
        eng.async_h2d = flag0


def test_engine_loads_padded_to_kernel_block(engine):
    """Every engine load pads S to the Pallas block multiple so any
    backend's grid is legal."""
    eng, ds = engine
    cache, _ = eng.store.load_batch(
        eng.models["sm"].cfg, Profile("sm", 0.0), _ids(ds, 3),
        pad_to_multiple=KERNEL_BLOCK_S, headroom=4, n_real=3)
    assert cache["k"].shape[2] % KERNEL_BLOCK_S == 0


# ---------------------------------------------------------------------------
# flush counters and spans
# ---------------------------------------------------------------------------

def test_two_batch_flush_counts_each_step(engine, monkeypatch):
    """A two-batch async run_filter spends host time in every step of a
    flush — shard reads, copying the items into the staging buffers, the
    copy to the device, the wait for the result — and prefetches the
    second batch while the first is in flight; padded_bytes is exactly
    the bytes of the padded caches load_batch returned (its lengths
    vector aside), and each load counts once as a staging reuse or
    allocation."""
    eng, ds = engine
    per_item = eng.store.item_nbytes(Profile("sm", 0.0))
    returned = []
    orig = eng.store.load_batch

    def spy(*a, **kw):
        cache, lengths = orig(*a, **kw)
        returned.append(cache)
        return cache, lengths

    monkeypatch.setattr(eng.store, "load_batch", spy)
    budget0, flag0 = eng.memory_budget, eng.async_h2d
    try:
        eng.memory_budget = 8 * per_item           # two flush batches
        eng.async_h2d = True
        c0 = eng.transfer_stats_local()
        eng.run_filter("sm", 0.0, _ids(ds, 16), [filter_query_token(4)],
                       TOK_YES, TOK_NO)
        d = eng.transfer_stats_local() - c0
    finally:
        eng.async_h2d = flag0
        eng.memory_budget = budget0
    assert len(returned) == 2
    for name in ("read_s", "assemble_s", "h2d_s", "wait_s", "prefetch_s"):
        assert getattr(d, name) > 0, name
    assert d.prefetch_s <= d.read_s + d.assemble_s + d.h2d_s
    assert d.padded_bytes == sum(int(v.nbytes) for cache in returned
                                 for k, v in cache.items()
                                 if k != "lengths")
    assert d.staged_reuses + d.staged_allocs == len(returned)


@pytest.mark.parametrize("device_cache", [False, True],
                         ids=["donated", "lru-held"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_async_two_batch_filter_matches_sync(engine, device_cache, quant):
    """The second batch is assembled in the other staging buffer while
    the first batch's copy to the device may still be in flight: the
    scores equal those of a synchronous run, whether the decode donates
    the copied caches or the device LRU holds them, for the float and
    the int8 profile."""
    eng, ds = engine
    ids = _ids(ds, 16)
    ratio = 0.5 if quant else 0.0
    query = [filter_query_token(7)]
    per_item = eng.store.item_nbytes(Profile("sm", ratio, quant), ids[0])
    budget0, flag0 = eng.memory_budget, eng.async_h2d
    try:
        eng.memory_budget = 8 * per_item           # two flush batches
        eng.device_cache = device_cache
        eng.async_h2d = False
        base = eng.run_filter("sm", ratio, ids, query, TOK_YES, TOK_NO,
                              quant=quant)
        eng.device_cache_clear()
        eng.async_h2d = True
        c0 = eng.transfer_stats_local()
        got = eng.run_filter("sm", ratio, ids, query, TOK_YES, TOK_NO,
                             quant=quant)
        d = eng.transfer_stats_local() - c0
    finally:
        eng.async_h2d = flag0
        eng.memory_budget = budget0
        eng.device_cache = False
        eng.device_cache_clear()
    np.testing.assert_array_equal(got, base)
    assert d.prefetch_s > 0
    assert d.staged_reuses + d.staged_allocs == 2
    assert (d.donated_bytes > 0) == (not device_cache)


def test_compiles_counted_on_a_new_shape_only(engine):
    """The first flush of a shape the engine has not run compiles its
    programs on the calling thread and counts them; the same flush again
    compiles nothing."""
    eng, ds = engine
    ids = _ids(ds, 5)
    query = [filter_query_token(5)] * 3            # a query length no
    #                                                other test flushes
    c0 = eng.transfer_stats_local()
    eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
    first = eng.transfer_stats_local() - c0
    eng.run_filter("sm", 0.0, ids, query, TOK_YES, TOK_NO)
    again = eng.transfer_stats_local() - c0 - first
    assert first.compiles >= 1 and first.compile_s > 0
    assert again.compiles == 0 and again.compile_s == 0


def test_flush_spans_nest_in_the_profiler_trace(engine, tmp_path):
    """The engine's spans land in the profiler's own trace: a flush holds
    its batch assembly, and both carry the execution id and operator of
    the runtime span they run in."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro import obs

    eng, ds = engine
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("stretto.run.flush", exec=7, op="probe"):
            eng.run_filter("sm", 0.0, _ids(ds, 4), [filter_query_token(6)],
                           TOK_YES, TOK_NO)
    events = {}
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("stretto."):
                    events.setdefault(ev.name, []).append(
                        (plane.name, line.name, ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    flushes = events["stretto.engine.flush"]
    assembles = events["stretto.store.assemble"]
    assert any(f[:2] == a[:2] and f[2] <= a[2] and a[3] <= f[3]
               for f in flushes for a in assembles)
    for name in ("stretto.engine.flush", "stretto.store.assemble"):
        for *_, stats in events[name]:
            assert stats["exec"] == 7 and stats["op"] == "probe", name
