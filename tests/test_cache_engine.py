"""KV-cache compression ladder + serving engine end-to-end behavior."""
import numpy as np
import pytest

from repro.cache.compression import prune_dominated
from repro.cache.store import CacheStore, Profile
from repro.data.synthetic import (TOK_NO, TOK_YES, filter_query_token,
                                  make_dataset, make_planted_params,
                                  map_query_token, planted_config,
                                  value_token)
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    ds = make_dataset("t", 80, seed=11)
    store = CacheStore(str(tmp_path_factory.mktemp("cache")))
    eng = ServingEngine(store)
    for size in ("sm", "lg"):
        cfg = planted_config(size)
        eng.register_model(size, cfg, make_planted_params(cfg, seed=1))
        eng.build_profiles(size, ds.items, ratios=[0.0, 0.5, 0.8],
                           prefill_batch=40)
    return eng, ds


def test_compressed_lengths(engine):
    eng, ds = engine
    s0 = eng.store.load(Profile("lg", 0.0), 0)
    s5 = eng.store.load(Profile("lg", 0.5), 0)
    s8 = eng.store.load(Profile("lg", 0.8), 0)
    n = len(ds.items[0].tokens)
    assert int(s0["__length__"]) == n
    assert int(s5["__length__"]) == max(4, round(0.5 * n))
    assert int(s8["__length__"]) == max(4, round(0.2 * n))
    # cache arrays shrink accordingly
    assert s5["k"].shape[1] < s0["k"].shape[1]
    assert s8["k"].shape[1] < s5["k"].shape[1]


def test_storage_shrinks_with_ratio(engine):
    eng, _ = engine
    b0 = eng.store.storage_bytes(Profile("lg", 0.0))
    b8 = eng.store.storage_bytes(Profile("lg", 0.8))
    assert b8 < 0.4 * b0


def test_quality_ladder_model_size(engine):
    """Gold (lg, r=0) must beat the small model on the planted filters."""
    eng, ds = engine
    ids = [it.item_id for it in ds.items]
    accs = {}
    for size in ("sm", "lg"):
        lo = eng.run_filter(size, 0.0, ids, [filter_query_token(1)],
                            TOK_YES, TOK_NO)
        labels = np.array([it.labels[1] for it in ds.items])
        accs[size] = ((lo > 0) == labels).mean()
    assert accs["lg"] > accs["sm"]
    assert accs["lg"] > 0.8


def test_quality_ladder_compression(engine):
    """Aggressive compression must hurt lg filter accuracy (the token-drop
    mechanism is real, not simulated)."""
    eng, ds = engine
    ids = [it.item_id for it in ds.items]
    labels = np.array([it.labels[1] for it in ds.items])
    acc = {}
    for r in (0.0, 0.8):
        lo = eng.run_filter("lg", r, ids, [filter_query_token(1)],
                            TOK_YES, TOK_NO)
        acc[r] = ((lo > 0) == labels).mean()
    assert acc[0.8] < acc[0.0]


def test_map_values(engine):
    eng, ds = engine
    ids = [it.item_id for it in ds.items]
    vals, conf = eng.run_map("lg", 0.0, ids, [map_query_token(2)],
                             [value_token(v) for v in range(8)])
    want = np.array([value_token(it.map_vals[2]) for it in ds.items])
    assert (vals == want).mean() > 0.9
    assert (conf > 0).all()


def test_padded_batching_consistent(engine):
    """Results must not depend on batch composition (padding is masked)."""
    eng, ds = engine
    ids = [it.item_id for it in ds.items[:16]]
    full = eng.run_filter("lg", 0.5, ids, [filter_query_token(3)],
                          TOK_YES, TOK_NO)
    solo = np.concatenate(
        [eng.run_filter("lg", 0.5, [i], [filter_query_token(3)],
                        TOK_YES, TOK_NO) for i in ids])
    np.testing.assert_allclose(full, solo, atol=2e-3)


def test_max_batch_grows_with_compression(engine):
    """The memory-budget -> max-batch computation (paper §5): higher
    compression means smaller per-item caches, hence larger batches —
    bounded above by the engine's max_batch and below by 1."""
    eng, ds = engine
    per_item = {r: sum(a.nbytes for k, a in
                       eng.store.load(Profile("lg", r), 0).items()
                       if k != "__length__")
                for r in (0.0, 0.5, 0.8)}
    assert per_item[0.8] < per_item[0.5] < per_item[0.0]
    budget0, cap0 = eng.memory_budget, eng.max_batch
    try:
        # budget sized so compression visibly widens the batch
        eng.memory_budget = 8 * per_item[0.0]
        bs = {r: eng.max_batch_for("lg", r) for r in (0.0, 0.5, 0.8)}
        assert bs[0.0] < bs[0.5] < bs[0.8]
        assert bs[0.0] == 8
        # never exceeds the configured hard cap ...
        eng.max_batch = 4
        assert all(eng.max_batch_for("lg", r) == 4 for r in (0.0, 0.5, 0.8))
        # ... never collapses below one even under an absurd budget
        eng.max_batch = cap0
        eng.memory_budget = 1
        assert all(eng.max_batch_for("lg", r) == 1 for r in (0.0, 0.5, 0.8))
        # unbounded budget saturates at the hard cap
        eng.memory_budget = 1e18
        assert eng.max_batch_for("lg", 0.8) == cap0
    finally:
        eng.memory_budget, eng.max_batch = budget0, cap0


def test_batch_size_respects_item_count(engine):
    """_batch_size (the online chunking) is the profile's max batch
    clipped to the actual batch of ids."""
    eng, ds = engine
    ids = [it.item_id for it in ds.items[:10]]
    assert eng._batch_size(Profile("lg", 0.0), ids) == 10
    budget0 = eng.memory_budget
    try:
        per_item = sum(a.nbytes for k, a in
                       eng.store.load(Profile("lg", 0.0), ids[0]).items()
                       if k != "__length__")
        eng.memory_budget = 3 * per_item
        assert eng._batch_size(Profile("lg", 0.0), ids) == 3
    finally:
        eng.memory_budget = budget0
    # operators surface the cap to the profiler/cost model
    from repro.serving.operators import KVCacheLLMOperator
    op = KVCacheLLMOperator(eng, "lg", 0.8)
    assert op.max_batch() == eng.max_batch_for("lg", 0.8)


def test_prune_dominated():
    profiles = [
        {"ratio": 0.0, "quality": 0.95, "cost": 10.0},
        {"ratio": 0.3, "quality": 0.94, "cost": 8.0},
        {"ratio": 0.5, "quality": 0.80, "cost": 9.0},   # dominated by r=.3
        {"ratio": 0.8, "quality": 0.60, "cost": 3.0},
    ]
    kept = prune_dominated(profiles)
    ratios = {p["ratio"] for p in kept}
    assert 0.5 not in ratios
    assert {0.0, 0.3, 0.8} <= ratios


def test_bf16_profile_survives_reopen(tmp_path):
    """A bf16 profile written by one store reads back as bf16, bit for
    bit, through a fresh CacheStore over the same directory (.npz alone
    would return raw 2-byte voids)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    k = rng.normal(size=(2, 12, 2, 16)).astype(jnp.bfloat16)
    v = rng.normal(size=(2, 12, 2, 16)).astype(jnp.bfloat16)
    q8 = rng.integers(-127, 128, size=(2, 12, 2, 16)).astype(np.int8)
    scale = rng.random((2, 12, 2)).astype(np.float32)
    CacheStore(str(tmp_path)).save(Profile("m", 0.5), 3, {"k": k, "v": v},
                                   12)
    CacheStore(str(tmp_path)).save(Profile("m", 0.5, quant=True), 3,
                                   {"k": q8, "k_scale": scale}, 12)
    fresh = CacheStore(str(tmp_path))
    got = fresh.load(Profile("m", 0.5), 3)
    assert got["k"].dtype == jnp.bfloat16 and got["v"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(got["k"].view(np.uint16),
                                  k.view(np.uint16))
    assert int(got["__length__"]) == 12
    quant = fresh.load(Profile("m", 0.5, quant=True), 3)
    assert quant["k"].dtype == np.int8
    assert quant["k_scale"].dtype == np.float32
    cache, _ = fresh.load_batch(planted_config("sm"), Profile("m", 0.5),
                                [3, 3])
    assert cache["k"].dtype == jnp.bfloat16
    assert cache["k"].shape == (2, 2, 32, 2, 16)
