"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax import; tests use the 1-device
default).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ('data', 'model') = 256 chips.
    Multi-pod:  (2, 16, 16) ('pod', 'data', 'model') = 512 chips — the 'pod'
    axis is the slow inter-pod (DCI) dimension; the SVRP anchor refresh is the
    only traffic that must cross it every round."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int | None = None):
    """Small host-device mesh for CPU tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model*(pod or 1))."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def make_sweep_mesh(devices=None):
    """1-D ('data',) mesh over `devices` (default: all local devices) — the
    trial-sharding mesh of the experiment engine's `run_batch(shard="data")`.

    Returned as a plain `jax.sharding.Mesh` (no Auto/Explicit axis types:
    the engine shard_maps every axis manually)."""
    import numpy as np

    devs = list(jax.devices()) if devices is None else list(devices)
    return jax.sharding.Mesh(np.array(devs), ("data",))


def make_client_mesh(devices=None):
    """1-D ('clients',) mesh over `devices` (default: all local devices) —
    the CLIENT-axis mesh of `run_batch(shard="clients")` (docs/SCALING.md).

    Same plain-`Mesh` convention as `make_sweep_mesh`: the substrate
    shard_maps the axis manually, laying each problem's client-major leaves
    (data blocks, DP noise shifts) over the devices in contiguous blocks."""
    import numpy as np

    devs = list(jax.devices()) if devices is None else list(devices)
    return jax.sharding.Mesh(np.array(devs), ("clients",))


def data_axis_names(mesh) -> tuple[str, ...]:
    """The client/cohort axes: ('pod', 'data') when multi-pod else ('data',)."""
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def num_cohorts(mesh) -> int:
    out = 1
    for n in data_axis_names(mesh):
        out *= mesh.shape[n]
    return out
