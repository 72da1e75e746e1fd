"""Batch assembly in `CacheStore.load_batch`: each item is copied once
into its slot of a host staging buffer that the loading thread reuses.

The batches must be bit-identical to padding each item to the batch's
S_max and stacking them (`_oracle`, the assembly the store used before),
whatever an earlier batch left in the buffer: every pad position reads 0.
A cache handed out earlier keeps its values while later loads refill the
buffers, and the staging counters count one per load.
"""
import ml_dtypes
import numpy as np
import pytest

from repro.cache.store import SEQ_KEYS, CacheStore, Profile
from repro.configs.base import ModelConfig
from repro.obs import FlushCounters

L, KV, DH = 2, 2, 8
PAD, HEADROOM = 8, 2
# the longest item sets S_max = 48 wherever it is in the batch
LENGTHS = [40, 8, 33, 17, 5, 12, 40, 24, 9, 3, 21, 30]
BF16 = Profile("staging", 0.0)
Q8 = Profile("staging", 0.5, quant=True)
CFG = ModelConfig(name="staging", family="dense", n_layers=L, d_model=16,
                  n_heads=2, n_kv_heads=KV, d_head=DH, d_ff=32,
                  vocab_size=64, attn_kind="gqa")

# load sequences: S_max shrinks after a longer batch, items move between
# slots, the batch width changes, and one shape comes back after others
SEQUENCE = [[0, 1, 2, 3], [3, 2, 1, 0], [1, 0, 3, 2], [6, 4, 5, 1],
            [4, 5, 7, 8], [8, 7, 5, 4], [9, 4, 8, 5], [0, 11, 10],
            [10, 9, 4, 3, 2, 1], [5, 9], [1, 2, 3, 0], [2, 6, 11, 7]]


def _shards(quant: bool, seed: int = 0):
    """Hand-made shards: bf16 k/v with a bf16 state that is not padded
    (as a hybrid model's conv state), or int8 k/v with float32 scales."""
    rng = np.random.default_rng(seed)
    out = []
    for n in LENGTHS:
        k, v = (rng.standard_normal((L, n, KV, DH), np.float32)
                for _ in range(2))
        if quant:
            out.append({"k": (k * 40).astype(np.int8),
                        "v": (v * 40).astype(np.int8),
                        "k_scale": rng.random((L, n, KV), np.float32),
                        "v_scale": rng.random((L, n, KV), np.float32)})
        else:
            out.append({"k": k.astype(ml_dtypes.bfloat16),
                        "v": v.astype(ml_dtypes.bfloat16),
                        "conv": rng.standard_normal((L, 3, 4)).astype(
                            ml_dtypes.bfloat16)})
    return out


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    st = CacheStore(str(tmp_path_factory.mktemp("staging")))
    for profile in (BF16, Q8):
        for i, arrays in enumerate(_shards(profile.quant)):
            st.save(profile, i, arrays, LENGTHS[i])
    return st


def _oracle(store, profile, ids):
    """Each item padded with zeros along S to the batch's S_max, then the
    items stacked on axis 1."""
    shards = [store.load(profile, i) for i in ids]
    smax = max(LENGTHS[i] for i in ids) + HEADROOM
    smax = -(-smax // PAD) * PAD
    out = {}
    for key in shards[0]:
        if key == "__length__":
            continue
        per = []
        for s in shards:
            a = s[key]
            if key in SEQ_KEYS:
                pad = [(0, 0)] * a.ndim
                pad[1] = (0, smax - a.shape[1])
                a = np.pad(a, pad)
            per.append(a)
        out[key] = np.stack(per, axis=1)
    return out


def _load(store, profile, ids, counters=None):
    cache, lengths = store.load_batch(CFG, profile, ids, pad_to_multiple=PAD,
                                      headroom=HEADROOM, counters=counters)
    np.testing.assert_array_equal(lengths, [LENGTHS[i] for i in ids])
    return cache


def _assert_bit_identical(cache, oracle, ids):
    assert set(cache) == set(oracle) | {"lengths"}
    for key, want in oracle.items():
        got = np.asarray(cache[key])
        assert got.dtype == want.dtype and got.shape == want.shape, key
        assert got.tobytes() == want.tobytes(), key
        if key in SEQ_KEYS:
            raw = got.view(f"u{got.dtype.itemsize}")
            for slot, i in enumerate(ids):
                assert not raw[:, slot, LENGTHS[i]:].any(), (key, slot)


@pytest.mark.parametrize("profile", [BF16, Q8], ids=["bf16", "int8"])
def test_batches_bit_identical_to_pad_then_stack(store, profile):
    for ids in SEQUENCE:
        _assert_bit_identical(_load(store, profile, ids),
                              _oracle(store, profile, ids), ids)


def test_profiles_interleaved_share_nothing(store):
    """Loads of two profiles in turn: keys of one dtype and shape then
    another land in the same thread's buffers (k and v are bf16 in one,
    int8 in the other)."""
    for ids in SEQUENCE:
        for profile in (BF16, Q8):
            _assert_bit_identical(_load(store, profile, ids),
                                  _oracle(store, profile, ids), ids)


@pytest.mark.parametrize("profile", [BF16, Q8], ids=["bf16", "int8"])
def test_held_cache_survives_two_later_loads(store, profile):
    """A cache held by its caller (as the device LRU holds it) keeps its
    values after two later loads of the same shape have refilled both
    buffers it could have shared memory with."""
    held_ids = [0, 1, 2, 3]
    _load(store, profile, [3, 2, 1, 0])    # both buffers hold this shape
    held = _load(store, profile, held_ids)
    c = FlushCounters()
    _load(store, profile, [6, 4, 5, 1], c)
    _load(store, profile, [2, 6, 11, 7], c)
    assert c.staged_reuses == 2 and c.staged_allocs == 0
    _assert_bit_identical(held, _oracle(store, profile, held_ids), held_ids)


def test_staging_counters_count_one_per_load(tmp_path):
    """Each load counts once: an allocation while either buffer of the
    thread's pair has yet to hold a batch that large, a reuse after."""
    st = CacheStore(str(tmp_path))
    for i, arrays in enumerate(_shards(False)):
        st.save(BF16, i, arrays, LENGTHS[i])
    c = FlushCounters()
    steps = [([0, 1, 2, 3], (0, 1)),        # first buffer of the pair
             ([3, 2, 1, 0], (0, 2)),        # second buffer
             ([1, 0, 3, 2], (1, 2)),        # the first again
             ([4, 5], (2, 2)),              # narrower and shorter
             ([0, 1, 2, 3, 4, 5], (2, 3)),  # wider: the first grows
             ([0, 1, 2, 3, 4, 6], (2, 4)),  # the second grows
             ([1, 2], (3, 4)),
             ([6, 5, 4, 3, 2, 1], (4, 4))]
    for ids, (reuses, allocs) in steps:
        _load(st, BF16, ids, c)
        assert (c.staged_reuses, c.staged_allocs) == (reuses, allocs), ids
    assert FlushCounters.from_dict(c.as_dict()) == c


def test_threads_stage_in_buffers_of_their_own(store):
    """Concurrent loads on several threads (the `threads` and `sharded`
    dispatchers) each assemble in their own buffers: every batch is still
    bit-identical."""
    import threading

    errors = []

    def run(offset):
        try:
            for j in range(3 * len(SEQUENCE)):
                ids = SEQUENCE[(j + offset) % len(SEQUENCE)]
                profile = (BF16, Q8)[(j + offset) % 2]
                _assert_bit_identical(_load(store, profile, ids),
                                      _oracle(store, profile, ids), ids)
        except BaseException as e:     # reported by the main thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=(o,)) for o in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
