"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; "pod" is a second,
slower data-parallel axis (DCN-ish links), so gradient reduction is
hierarchical: reduce-scatter over ``data`` intra-pod, all-reduce over
``pod`` inter-pod — GSPMD derives that from the (pod, data) batch axes.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — the dry-run sets
XLA_FLAGS before importing anything).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic re-mesh path, smoke meshes). Every axis
    is Auto: the layer code places arrays with sharding constraints,
    which may only name Auto axes (``jax.make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def required_devices(multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256


def make_smoke_mesh(data: Optional[int] = None, model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests; 1 CPU → (1, 1))."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
