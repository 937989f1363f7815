"""Production meshes (DESIGN.md §7).

Single pod: (data=16, model=16) = 256 chips (TPU v5e).  Multi-pod:
(pod=2, data=16, model=16) = 512 chips; the ``pod`` axis extends data
parallelism over the inter-pod link.  A function, not a module constant —
importing this module must never touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  JAX 0.9 makes mesh axes Explicit
    by default, which types every array's sharding: the engine's metrics
    slice-back and GSPMD-sharded models then fail to trace on them."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (same axis names as single pod)."""
    return _mesh((1, 1), ("data", "model"))


def make_grid_mesh(num_devices: int | None = None):
    """1-D ``("data",)`` mesh over the visible devices for grid-sharded
    FL experiment sweeps (``ExperimentEngine(mesh=...)``).

    The engine's grid axis resolves through the ``"grid"`` rule in
    ``sharding.rules.TRAIN_RULES`` — ``("pod", "data")`` — so this mesh
    shards a (strategy x seed x scenario) grid over every device; on a
    1-device host the engine falls back to the plain vmapped program.
    """
    n = num_devices if num_devices is not None else len(jax.devices())
    return _mesh((n,), ("data",))
