"""Small 1-D data mesh for sharded query execution.

`launch/mesh.py` builds the production 2-D (data, model) meshes and
insists on 256/512-device slices; query sharding needs the opposite — a
tiny 1-D mesh over however many devices this host actually has (CPU CI
simulates them with `XLA_FLAGS=--xla_force_host_platform_device_count=N`,
which must be set before the first jax import — see tests/conftest.py).

`Settings.shards` semantics: 1 = single-device (no mesh, no shard_map),
0 = auto (every local device), n>1 = exactly n devices (error when the
host has fewer — silently running a different mesh shape would silently
change the plan-cache key and the per-shard capacities).
"""
from __future__ import annotations

import numpy as np

_MESHES: dict[int, object] = {}

AXIS = "data"


def resolve_shards(settings) -> int:
    """Concrete shard count for `settings` (0 = all local devices)."""
    n = int(getattr(settings, "shards", 1) or 0)
    if n == 1:
        return 1
    import jax

    avail = len(jax.devices())
    if n == 0:
        return avail
    if n > avail:
        raise ValueError(
            f"settings.shards={n} but only {avail} devices are visible "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=… "
            f"before importing jax to simulate more on CPU)")
    return n


def data_mesh(n: int):
    """1-D mesh over the first `n` local devices, axis name 'data'."""
    got = _MESHES.get(n)
    if got is not None:
        return got
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:n]), (AXIS,))
    _MESHES[n] = mesh
    return mesh

