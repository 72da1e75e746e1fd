"""MeshDispatcher tests: spec resolution, shard-bounds tiling (property
tests for Sharded AND Mesh dispatchers), device placement plumbing, and
the core guarantee — mesh:N decisions / map values / per-stage telemetry
bit-identical to inline through the real serving engine.

The parity tests here run on however many devices the host exposes (the
CI mesh-parity job forces 8 via XLA_FLAGS=--xla_force_host_platform_
device_count=8 in the job env — the flag must precede the first jax
import, so it cannot be set inside a test); on a 1-device host the mesh
degenerates to the sharded scatter and every assertion still holds.
"""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

import jax

from repro.launch.mesh import make_dispatch_mesh, make_local_mesh, peaks_for
from repro.runtime.dispatch import (MeshDispatcher, ShardedDispatcher,
                                    backend_engines, resolve_dispatcher)


# ---------------------------------------------------------------------------
# spec resolution + mesh construction
# ---------------------------------------------------------------------------

def test_resolve_mesh_specs():
    d, owned = resolve_dispatcher("mesh:8")
    assert isinstance(d, MeshDispatcher) and owned
    assert d.name == "mesh"
    assert d.n_shards == 8 and d.n_workers == 8
    d, _ = resolve_dispatcher("mesh")          # bare: every local device
    assert d.n_shards == jax.local_device_count()
    with pytest.raises(ValueError, match="must be positive"):
        resolve_dispatcher("mesh:0")


def test_dispatch_mesh_axes_and_size():
    """The dispatch mesh carries the production axis names (so the
    logical-axis sharding rules resolve identically) and never exceeds
    the host's device count."""
    n_dev = jax.local_device_count()
    for n in (1, 2, 8):
        mesh = make_dispatch_mesh(n)
        assert set(mesh.axis_names) == {"data", "model"}
        assert mesh.devices.size <= n_dev
    d = MeshDispatcher(8)
    assert d.mesh.devices.size <= n_dev
    # shards cycle over the data-axis slices: every shard resolves to a
    # real device, and with >=2 devices distinct slices get distinct
    # shards
    devs = [d.shard_device(i) for i in range(8)]
    assert all(dev in jax.devices() for dev in devs)
    if n_dev >= 2:
        assert len(set(devs)) >= 2


def test_meshes_use_auto_axes():
    """Sharding rules place arrays with with_sharding_constraint, which
    Explicit mesh axes (jax.make_mesh's default) refuse."""
    from jax.sharding import AxisType
    for mesh in (make_local_mesh(), make_dispatch_mesh(2)):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_peak_table_keyed_by_device_kind():
    """Rooflines price against the running device's own peaks; a device
    the table does not list is an error, never a fallback."""
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9) and v5e.source
    assert peaks_for() == peaks_for(jax.devices()[0].device_kind)
    with pytest.raises(ValueError, match="no peak numbers"):
        peaks_for("TPU v99 unknown")


# ---------------------------------------------------------------------------
# shard_bounds tiles any corpus exactly (Sharded and Mesh dispatchers)
# ---------------------------------------------------------------------------

def _check_bounds_tile(disp, n):
    bounds = disp.shard_bounds(n)
    covered = [i for lo, hi in bounds for i in range(lo, hi)]
    assert covered == list(range(n)), \
        f"{disp.name}:{disp.n_shards} bounds {bounds} do not tile {n}"
    assert all(lo < hi for lo, hi in bounds)          # no empty shards
    assert len(bounds) <= max(disp.n_shards, 1)


@pytest.mark.parametrize("n", [0, 1, 2, 3, 7, 8, 9, 64, 100])
@pytest.mark.parametrize("shards", [1, 2, 3, 8, 16])
def test_shard_bounds_tile_exactly(n, shards):
    """Including n=0 and n_items < n_shards, for both dispatcher kinds."""
    _check_bounds_tile(ShardedDispatcher(shards), n)
    _check_bounds_tile(MeshDispatcher(shards), n)


@given(n=st.integers(0, 200), shards=st.integers(1, 16))
@settings(max_examples=60, deadline=None)
def test_shard_bounds_tile_property(n, shards):
    _check_bounds_tile(ShardedDispatcher(shards), n)
    _check_bounds_tile(MeshDispatcher(shards), n)


# ---------------------------------------------------------------------------
# shard_context placement plumbing
# ---------------------------------------------------------------------------

class _FakeEngine:
    def __init__(self):
        self.placed = []

    def place_on(self, device, sharding=None):
        import contextlib

        @contextlib.contextmanager
        def ctx():
            self.placed.append((device, sharding))
            yield
        return ctx()


class _FakeBackend:
    def __init__(self, engine):
        self.engine = engine


def test_shard_context_places_engines_per_device():
    d = MeshDispatcher(4)
    eng = _FakeEngine()
    for i in range(4):
        with d.shard_context(i, _FakeBackend(eng)):
            pass
    assert len(eng.placed) == 4
    for i, (dev, sharding) in enumerate(eng.placed):
        assert dev == d.shard_device(i)
        # params placement resolves through the logical-axis rules to a
        # replicated NamedSharding pinned on that shard's device
        assert isinstance(sharding, jax.sharding.NamedSharding)
        assert sharding.spec == jax.sharding.PartitionSpec()
        assert set(sharding.mesh.axis_names) == {"data", "model"}
        assert sharding.mesh.devices.flatten().tolist() == [dev]


def test_backend_engines_discovery():
    eng_a, eng_b = _FakeEngine(), _FakeEngine()

    class _Pool:
        members = {"a": _FakeBackend(eng_a), "b": _FakeBackend(eng_b)}

    assert backend_engines(_FakeBackend(eng_a)) == [eng_a]
    assert backend_engines(_Pool()) == [eng_a, eng_b]
    assert backend_engines(object()) == []


# ---------------------------------------------------------------------------
# end-to-end parity through the real serving engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sports_frame(tmp_path_factory):
    from repro.api import Session, SessionConfig
    from repro.core import PlannerConfig
    from repro.data.synthetic import make_dataset
    ds = make_dataset("mesh-parity", 60, seed=5)
    sess = Session(SessionConfig(
        cache_dir=str(tmp_path_factory.mktemp("cache")),
        profile_ratios=(0.0, 0.8), models=("sm",),
        sm_ratios=(0.8, 0.0), lg_ratios=(0.8,),
        planner=PlannerConfig(steps=120, restarts=2, snapshots=2),
        sample_frac=0.35, partition_size=20))
    sess.prepare(ds.items)
    frame = (sess.frame(ds.items)
             .sem_filter("about sports?", task_id=1)
             .sem_map("which group?", task_id=3)
             .with_guarantees(recall=0.7, precision=0.7))
    yield frame
    sess.close()


def test_mesh_bit_identical_to_inline(sports_frame):
    """The acceptance criterion: decisions, map values and the per-stage
    EXPLAIN ANALYZE counters (n_tuples / n_llm_calls / kv_bytes) of a
    mesh:8 run match inline bit-for-bit. n_batches is NOT compared —
    shards flush independently, so the batch count legitimately differs;
    the scored-tuple and byte counters may not."""
    r_inline = sports_frame.execute(dispatcher="inline")
    r_mesh = sports_frame.execute(dispatcher="mesh:8")
    a, b = r_inline.raw, r_mesh.raw

    np.testing.assert_array_equal(a.accepted, b.accepted)
    assert set(a.map_values) == set(b.map_values)
    for li in a.map_values:
        np.testing.assert_array_equal(a.map_values[li], b.map_values[li])

    key = lambda sg: (sg.logical_idx, sg.stage, sg.op_name)
    sa = {key(sg): sg for sg in a.stage_stats}
    sb = {key(sg): sg for sg in b.stage_stats}
    assert set(sa) == set(sb)
    for k in sa:
        assert sa[k].n_tuples == sb[k].n_tuples, k
        assert sa[k].n_llm_calls == sb[k].n_llm_calls, k
        assert sa[k].kv_bytes == sb[k].kv_bytes, k

    # the ANALYZE rendering names the dispatcher that actually ran it
    txt = str(r_mesh.explain_analyze())
    assert "dispatcher=mesh" in txt


def test_mesh_wall_clock_reported(sports_frame):
    """A mesh scatter reports elapsed wall_s separately from summed
    runtime_s; with >1 worker overlapping shards, wall must not exceed
    the sum by much (overlap is the whole point of the scatter)."""
    r = sports_frame.execute(dispatcher="mesh:4").raw
    assert r.dispatcher == "mesh" and r.n_workers == 4
    assert r.wall_s > 0 and r.runtime_s > 0
    assert r.wall_s <= r.runtime_s * 1.5    # generous: tiny corpora jitter
