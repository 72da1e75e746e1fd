"""Production mesh definitions + the hardware peak numbers.

Mesh builders are functions, not module-level constants, so importing
this module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else sees the real device count.

This module is also the single source of hardware peak numbers: the
dry-run roofline (launch/dryrun.py) and the kernels perf gate
(benchmarks/roofline.py) both price against the `HardwarePeaks` that
`peaks_for()` looks up by `device_kind`. A device that is not in the
table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> "jax.sharding.Mesh":
    """`jax.make_mesh` with Auto axes: the sharding rules place arrays
    through `with_sharding_constraint`, which Explicit axes (the
    default of `jax.make_mesh`) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> "jax.sharding.Mesh":
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh() -> "jax.sharding.Mesh":
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


def make_dispatch_mesh(n_shards: int) -> "jax.sharding.Mesh":
    """The runtime's data-parallel dispatch mesh (MeshDispatcher): up to
    `n_shards` devices on the "data" axis, model axis 1-wide. Degenerates
    to the local 1-device mesh on single-device hosts, and promotes to
    the full production mesh when the host actually has a pod's worth of
    chips — the same axis names either way, so the logical-axis sharding
    rules (distributed/sharding.py) resolve identically."""
    import numpy as np
    devs = jax.devices()
    if len(devs) <= 1 or n_shards <= 1:
        return make_local_mesh()
    if n_shards >= 256 and len(devs) >= 256:
        return make_production_mesh()
    n = min(int(n_shards), len(devs))
    return jax.sharding.Mesh(np.asarray(devs[:n]).reshape(n, 1),
                             ("data", "model"))


@dataclass(frozen=True)
class HardwarePeaks:
    """One hardware peak set a roofline can price against."""
    name: str           # which peak set this is ("tpu-v5e", "ci-cpu", ...)
    flops: float        # FLOP/s (per chip)
    hbm_bw: float       # B/s (per chip)
    ici_bw: float = 0.0  # B/s per interconnect link (0: single chip)
    source: str = ""


# Per-chip peaks keyed by `jax.Device.device_kind`.
PEAKS_BY_KIND = {
    "TPU v5 lite": HardwarePeaks(
        "tpu-v5e", flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI over 4 links"),
    # deliberately conservative CPU-class bound for CI runners: a CPU
    # roofline is a sanity bound on the runner, never a device metric
    "cpu": HardwarePeaks("ci-cpu", flops=100e9, hbm_bw=20e9,
                         source="conservative CI-runner bound"),
}
TPU_V5E = PEAKS_BY_KIND["TPU v5 lite"]


def peaks_for(device_kind: Optional[str] = None) -> HardwarePeaks:
    """The peak set of `device_kind` (default: the first JAX device's).
    Raises ValueError for a device the table does not list."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak numbers for device_kind {device_kind!r}; add its "
            f"published peaks to PEAKS_BY_KIND (known: "
            f"{sorted(PEAKS_BY_KIND)})") from None
