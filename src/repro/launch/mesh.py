"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis in the Auto sharding mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """single-pod: (data=16, model=16) = 256 chips;
    multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-chip mesh with the production axis names (tests/smoke runs)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_blocks_mesh(n_shards: int | None = None):
    """1-D ``("blocks",)`` mesh for the sharded partitioner superstep.

    The graph workload shards its vertex-block axis, not model/data, so it
    gets its own mesh builder. ``n_shards=None`` takes every visible device;
    an explicit count takes the first ``n_shards`` (scaling benchmarks sweep
    1/2/4/8 on a fixed device pool).
    """
    import numpy as np

    devices = jax.devices()
    if n_shards is None:
        n_shards = len(devices)
    if not 1 <= n_shards <= len(devices):
        raise ValueError(
            f"n_shards={n_shards} not in [1, {len(devices)}] visible devices")
    return jax.sharding.Mesh(np.asarray(devices[:n_shards]), ("blocks",))
