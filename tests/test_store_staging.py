"""Batch assembly in `CacheStore.load_batch`: each distinct item is copied
once into a host staging buffer that the loading thread reuses; sequence
keys cross to the device unpadded, items back to back, and are padded
there.

The batches must be bit-identical to padding each item to the batch's
S_max and stacking them (`_oracle`, the assembly the store used before),
whatever an earlier batch left in the buffers: every pad position reads
exactly 0, made on the device. A cache handed out earlier keeps its
values while later loads refill the buffers, the staging counters count
one per load, `h2d_bytes` counts the bytes of the flat buffers sent, and
the layout program's dispatch and compile count in `layout_s`.
"""
import math

import ml_dtypes
import numpy as np
import pytest

from repro.cache.store import SEQ_KEYS, CacheStore, Profile, _flat_len
from repro.configs.base import ModelConfig
from repro.obs import FlushCounters

L, KV, DH = 2, 2, 8
PAD, HEADROOM = 8, 2
# the longest item sets S_max = 48 wherever it is in the batch
LENGTHS = [40, 8, 33, 17, 5, 12, 40, 24, 9, 3, 21, 30]
BF16 = Profile("staging", 0.0)
Q8 = Profile("staging", 0.5, quant=True)
MLA = Profile("staging_mla", 0.0)
HYBRID = Profile("staging_hybrid", 0.0)
PROFILES = [BF16, Q8, MLA, HYBRID]
PROFILE_IDS = ["bf16", "int8", "mla", "hybrid"]
CFG = ModelConfig(name="staging", family="dense", n_layers=L, d_model=16,
                  n_heads=2, n_kv_heads=KV, d_head=DH, d_ff=32,
                  vocab_size=64, attn_kind="gqa")

# load sequences: S_max shrinks after a longer batch, items move between
# slots, the batch width changes, and one shape comes back after others
SEQUENCE = [[0, 1, 2, 3], [3, 2, 1, 0], [1, 0, 3, 2], [6, 4, 5, 1],
            [4, 5, 7, 8], [8, 7, 5, 4], [9, 4, 8, 5], [0, 11, 10],
            [10, 9, 4, 3, 2, 1], [5, 9], [1, 2, 3, 0], [2, 6, 11, 7]]


def _shards(profile: Profile, seed: int = 0):
    """Hand-made shards: bf16 k/v; int8 k/v with float32 scales; an
    MLA-shaped latent cache (c_kv, k_rope); or bf16 k/v with a bf16 state
    that is not a sequence (as a hybrid model's conv state)."""
    rng = np.random.default_rng(seed)
    out = []
    for n in LENGTHS:
        k, v = (rng.standard_normal((L, n, KV, DH), np.float32)
                for _ in range(2))
        if profile is Q8:
            out.append({"k": (k * 40).astype(np.int8),
                        "v": (v * 40).astype(np.int8),
                        "k_scale": rng.random((L, n, KV), np.float32),
                        "v_scale": rng.random((L, n, KV), np.float32)})
        elif profile is MLA:
            out.append({"c_kv": rng.standard_normal((L, n, 12)).astype(
                            ml_dtypes.bfloat16),
                        "k_rope": rng.standard_normal((L, n, 4)).astype(
                            ml_dtypes.bfloat16)})
        else:
            arrays = {"k": k.astype(ml_dtypes.bfloat16),
                      "v": v.astype(ml_dtypes.bfloat16)}
            if profile is HYBRID:
                arrays["conv"] = rng.standard_normal((L, 3, 4)).astype(
                    ml_dtypes.bfloat16)
            out.append(arrays)
    return out


def _save_all(st):
    for profile in PROFILES:
        for i, arrays in enumerate(_shards(profile)):
            st.save(profile, i, arrays, LENGTHS[i])
    return st


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    return _save_all(CacheStore(str(tmp_path_factory.mktemp("staging"))))


def _smax(ids, headroom=HEADROOM):
    return -(-(max(LENGTHS[i] for i in ids) + headroom) // PAD) * PAD


def _oracle(store, profile, ids, headroom=HEADROOM):
    """Each item padded with zeros along S to the batch's S_max, then the
    items stacked on axis 1."""
    shards = [store.load(profile, i) for i in ids]
    smax = _smax(ids, headroom)
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


def _load(store, profile, ids, counters=None, headroom=HEADROOM):
    cache, lengths = store.load_batch(CFG, profile, ids, pad_to_multiple=PAD,
                                      headroom=headroom, counters=counters)
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


def _h2d_bytes(store, profile, ids, headroom=HEADROOM):
    """The bytes of the buffers a load sends: each sequence key's distinct
    items back to back in a flat buffer, the longest item's slice of S_max
    last, rounded by `_flat_len`; other keys stacked; the item spans once
    (every key here has the same lengths) and the lengths vector."""
    shards = [store.load(profile, i) for i in ids]
    distinct = [store.load(profile, i) for i in dict.fromkeys(ids)]
    ns = [LENGTHS[i] for i in dict.fromkeys(ids)]
    flat_len = _flat_len(sum(ns) - max(ns) + _smax(ids, headroom), PAD)
    total, seq = 0, False
    for key, a in distinct[0].items():
        if key == "__length__":
            continue
        if key in SEQ_KEYS:
            seq = True
            total += (a.shape[0] * flat_len * math.prod(a.shape[2:])
                      * a.dtype.itemsize)
        else:
            total += sum(s[key].nbytes for s in shards)
    return total + (2 * len(ids) * 4 if seq else 0) + len(ids) * 4


@pytest.mark.parametrize("profile", PROFILES, ids=PROFILE_IDS)
def test_batches_bit_identical_to_pad_then_stack(store, profile):
    for ids in SEQUENCE:
        _assert_bit_identical(_load(store, profile, ids),
                              _oracle(store, profile, ids), ids)


@pytest.mark.parametrize("profile", PROFILES, ids=PROFILE_IDS)
def test_item_at_s_max_fills_its_row(store, profile):
    """With no headroom, the longest items (40 positions, a multiple of
    the pad) fill S_max exactly, beside items of every other length."""
    for ids in ([0, 1, 2, 3], [4, 6, 9, 0, 11], [6], [0, 6, 3]):
        assert _smax(ids, 0) == 40
        _assert_bit_identical(_load(store, profile, ids, headroom=0),
                              _oracle(store, profile, ids, headroom=0), ids)


@pytest.mark.parametrize("profile", PROFILES, ids=PROFILE_IDS)
def test_pads_are_zero_whatever_the_buffers_hold(store, profile):
    """Every byte of the thread's staging buffers set to 0xFF (NaN in
    bfloat16 and float32) between loads: the positions past each item,
    which the host never writes, still read exactly 0 on the device."""
    _load(store, profile, SEQUENCE[0])     # this thread's buffers exist
    for ids in SEQUENCE:
        for pair in store._tl.staging.values():
            for buf in pair:
                buf.wait()
                buf.buf[:] = 0xFF
        _assert_bit_identical(_load(store, profile, ids),
                              _oracle(store, profile, ids), ids)


def test_profiles_interleaved_share_nothing(store):
    """Loads of the profiles in turn: keys of one dtype and shape then
    another land in the same thread's buffers (k and v are bf16 in one,
    int8 in another)."""
    for ids in SEQUENCE:
        for profile in PROFILES:
            _assert_bit_identical(_load(store, profile, ids),
                                  _oracle(store, profile, ids), ids)


@pytest.mark.parametrize("profile", [BF16, Q8], ids=["bf16", "int8"])
def test_held_cache_survives_two_later_loads(store, profile):
    """A cache held by its caller (as the device LRU holds it) keeps its
    values after two later loads no longer than it have refilled both
    buffers it could have shared memory with. Flat lengths (positions)
    are in the comments."""
    held_ids = [0, 1, 2, 3]
    _load(store, profile, [3, 2, 1, 0])    # 112: one buffer this long
    held = _load(store, profile, held_ids)  # 112: and the other
    c = FlushCounters()
    _load(store, profile, [6, 4, 5, 1], c)  # 80
    _load(store, profile, [2, 6, 9, 7], c)  # 112
    assert c.staged_reuses == 2 and c.staged_allocs == 0
    _assert_bit_identical(held, _oracle(store, profile, held_ids), held_ids)


def test_staging_counters_count_one_per_load(tmp_path):
    """Each load counts once: an allocation while either buffer of the
    thread's pair has yet to hold a flat batch that long, a reuse after.
    Flat lengths (positions) are in the comments."""
    st = CacheStore(str(tmp_path))
    for i, arrays in enumerate(_shards(BF16)):
        st.save(BF16, i, arrays, LENGTHS[i])
    c = FlushCounters()
    steps = [([0, 1, 2, 3], (0, 1)),        # 112: first buffer of the pair
             ([3, 2, 1, 0], (0, 2)),        # 112: second buffer
             ([1, 0, 3, 2], (1, 2)),        # 112: the first again
             ([4, 5], (2, 2)),              # 24: narrower and shorter
             ([0, 1, 2, 3, 4, 5], (2, 3)),  # 128: the first grows
             ([0, 1, 2, 3, 4, 6], (2, 4)),  # 152: the second grows
             ([1, 2], (3, 4)),              # 48
             ([6, 5, 4, 3, 2, 1], (4, 4))]  # 128
    for ids, (reuses, allocs) in steps:
        _load(st, BF16, ids, c)
        assert (c.staged_reuses, c.staged_allocs) == (reuses, allocs), ids
    assert FlushCounters.from_dict(c.as_dict()) == c


@pytest.mark.parametrize("profile", PROFILES, ids=PROFILE_IDS)
def test_h2d_bytes_are_the_flat_buffers(store, profile):
    """`h2d_bytes` counts what crossed to the device: the flat buffers,
    stacked keys, spans and lengths, about half of `padded_bytes` for the
    sequence keys here."""
    for ids in SEQUENCE:
        c = FlushCounters()
        cache = _load(store, profile, ids, c)
        assert c.h2d_bytes == _h2d_bytes(store, profile, ids), ids
        assert c.padded_bytes == sum(int(v.nbytes) for k, v in cache.items()
                                     if k != "lengths")
        assert c.h2d_bytes < c.padded_bytes


@pytest.mark.parametrize("profile", PROFILES, ids=PROFILE_IDS)
def test_layout_compiles_count_in_layout_s(store, profile):
    """A load of a flat length not met before compiles its layout program
    inside `stretto.store.layout`, so `layout_s` holds those compile
    seconds (and `h2d_s` does not need to); the same load again compiles
    nothing."""
    from repro import obs

    ids = [7, 2, 9, 4, 10]
    headroom = 100 + PAD * PROFILES.index(profile)   # an S_max of its own
    c = FlushCounters()
    n0, s0 = obs.compiles_here()
    cache = _load(store, profile, ids, c, headroom=headroom)
    n1, s1 = obs.compiles_here()
    assert n1 > n0
    assert c.layout_s >= s1 - s0 > 0
    again = FlushCounters()
    _load(store, profile, ids, again, headroom=headroom)
    assert obs.compiles_here()[0] == n1
    assert again.layout_s < c.layout_s
    _assert_bit_identical(cache, _oracle(store, profile, ids, headroom), ids)


def test_replicated_item_crosses_once(store):
    """The engine rounds a batch up by repeating its first item: the
    repeats point at the one copy sent, and the batch is unchanged."""
    ids = [3, 1, 2] + [3] * 5
    c = FlushCounters()
    _assert_bit_identical(_load(store, BF16, ids, c),
                          _oracle(store, BF16, ids), ids)
    assert c.h2d_bytes == _h2d_bytes(store, BF16, ids)
    one = FlushCounters()
    _load(store, BF16, [3, 1, 2], one)
    # the replicas add only their spans and lengths (12 bytes each)
    assert c.h2d_bytes - one.h2d_bytes == 5 * 12


def test_flat_lengths_few_and_close_to_the_items():
    """Over a sweep of batch compositions at the cells' lengths (64-512 in
    64-position pages, up to 64 items, padded to 128 with 3 positions of
    headroom), each flat length is less than 1/16 or one multiple of 128
    above what the batch needs, and few distinct lengths occur: 16 an
    octave at most."""
    rng = np.random.default_rng(7)
    need, got = [], []
    for _ in range(3000):
        b = int(rng.integers(1, 65))
        ns = rng.integers(1, 9, b) * 64
        smax = -(-(int(ns.max()) + 3) // 128) * 128
        need.append(int(ns.sum() - ns.max()) + smax)
        got.append(_flat_len(need[-1], 128))
    need, got = np.array(need), np.array(got)
    assert (got >= need).all()
    assert (got - need < np.maximum(need / 16, 128)).all()
    octaves = math.log2(got.max() / got.min())
    assert len(set(got.tolist())) <= 16 * math.ceil(octaves)
    # below 32 multiples the length is only rounded to the multiple
    assert [_flat_len(n, 128) for n in (1, 128, 129, 4095, 4096)] == \
        [128, 128, 256, 4096, 4096]
    assert _flat_len(4097, 128) == 4096 + 256


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
                profile = PROFILES[(j + offset) % len(PROFILES)]
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
