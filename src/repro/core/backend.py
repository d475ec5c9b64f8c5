"""Backend shim: the staging walker runs twice — once eagerly on numpy
(8-row samples, to collect the input set and exercise static decisions) and
once under jax tracing (the real staged program).  This shim abstracts the
handful of ops whose spelling differs."""
from __future__ import annotations

import numpy as np


class NumpyBackend:
    name = "numpy"
    xp = np

    @staticmethod
    def take(arr, idx):
        n = arr.shape[0]
        if n == 0:  # collection walk over an empty sample slice
            return np.zeros((len(idx),) + arr.shape[1:], dtype=arr.dtype)
        return arr[np.clip(idx, 0, n - 1)]

    @staticmethod
    def segment_sum(data, ids, n):
        out = np.zeros((n,) + data.shape[1:], dtype=data.dtype)
        np.add.at(out, np.clip(ids, 0, n - 1), data)
        return out

    @staticmethod
    def segment_max(data, ids, n, fill):
        out = np.full((n,) + data.shape[1:], fill, dtype=data.dtype)
        np.maximum.at(out, np.clip(ids, 0, n - 1), data)
        return out

    @staticmethod
    def segment_min(data, ids, n, fill):
        out = np.full((n,) + data.shape[1:], fill, dtype=data.dtype)
        np.minimum.at(out, np.clip(ids, 0, n - 1), data)
        return out

    @staticmethod
    def lexsort(keys):
        return np.lexsort(tuple(keys))

    @staticmethod
    def compact(mask, capacity):
        """(idx int32[capacity], count int32): row ids of the mask's valid
        rows, in order, zero-padded past `count`.  `count` may exceed
        `capacity` (the caller's overflow signal); the surplus rows are
        dropped from idx."""
        valid = np.flatnonzero(mask).astype(np.int32)
        count = np.int32(valid.size)
        idx = np.zeros((capacity,), dtype=np.int32)
        k = min(capacity, valid.size)
        idx[:k] = valid[:k]
        return idx, count

    @staticmethod
    def barrier(x):
        return x

    @staticmethod
    def searchsorted(a, v):
        return np.searchsorted(a, v)

    # -- mesh collectives: identity on the single-sample collection walk,
    # -- so sharded staging decisions see a plain one-shard world
    @staticmethod
    def psum(x, axis):
        return x

    @staticmethod
    def pmax(x, axis):
        return x

    @staticmethod
    def pmin(x, axis):
        return x

    @staticmethod
    def all_gather(x, axis, tiled=False):
        return x if tiled else np.asarray(x)[None]

    @staticmethod
    def axis_index(axis):
        return np.int32(0)


class JaxBackend:
    name = "jax"

    def __init__(self):
        import jax
        import jax.numpy as jnp

        self.xp = jnp
        self._jax = jax

    def take(self, arr, idx):
        # jnp gather clamps out-of-bounds indices by default
        return arr[idx]

    def segment_sum(self, data, ids, n):
        import jax

        return jax.ops.segment_sum(data, ids, num_segments=n)

    def segment_max(self, data, ids, n, fill):
        import jax
        import jax.numpy as jnp

        out = jax.ops.segment_max(data, ids, num_segments=n)
        # segment_max fills empty segments with -inf/min; normalize to fill
        neutral = jnp.asarray(fill, dtype=data.dtype)
        lo = -jnp.inf if data.dtype.kind == "f" else jnp.iinfo(data.dtype).min
        return jnp.where(out == lo, neutral, out)

    def segment_min(self, data, ids, n, fill):
        import jax
        import jax.numpy as jnp

        out = jax.ops.segment_min(data, ids, num_segments=n)
        neutral = jnp.asarray(fill, dtype=data.dtype)
        hi = jnp.inf if data.dtype.kind == "f" else jnp.iinfo(data.dtype).max
        return jnp.where(out == hi, neutral, out)

    def lexsort(self, keys):
        import jax.numpy as jnp

        return jnp.lexsort(tuple(keys))

    def compact(self, mask, capacity):
        """Cumsum + binary-search compaction (vmap-safe, static shapes).

        `cumsum(mask)` is non-decreasing, so the row id of the j-th valid
        row is the first position where the running count reaches j+1 — a
        vectorized `searchsorted` over the `capacity` output slots.  This
        is a pure gather formulation: XLA's CPU scatter executes updates
        serially (~100x slower than the rest of the pipeline combined),
        while cumsum + batched binary search stay vectorized.  Slots past
        the valid count search past the end and clamp to n-1; the caller's
        pad mask (`arange(capacity) < count`) hides them, and
        `count > capacity` is the overflow flag.
        """
        import jax.numpy as jnp

        c = jnp.cumsum(mask.astype(jnp.int32))
        count = c[-1]
        idx = jnp.searchsorted(
            c, jnp.arange(1, capacity + 1, dtype=jnp.int32))
        n = mask.shape[0]
        return jnp.clip(idx, 0, n - 1).astype(jnp.int32), count

    def barrier(self, x):
        import jax

        return jax.lax.optimization_barrier(x)

    def searchsorted(self, a, v):
        import jax.numpy as jnp

        return jnp.searchsorted(a, v)

    # -- mesh collectives (only traced inside shard_map: `axis` must be a
    # -- bound mesh axis name, which compile.py guarantees by setting
    # -- StageCtx.axis iff the staged fn is shard_map-wrapped).  The
    # -- combines stage under `op.combine`, so the device trace tells their
    # -- time from the operator's own work.
    def _combine(self, op, x, axis):
        with self._jax.named_scope("op.combine"):
            return op(x, axis)

    def psum(self, x, axis):
        return self._combine(self._jax.lax.psum, x, axis)

    def pmax(self, x, axis):
        return self._combine(self._jax.lax.pmax, x, axis)

    def pmin(self, x, axis):
        return self._combine(self._jax.lax.pmin, x, axis)

    def all_gather(self, x, axis, tiled=False):
        return self._jax.lax.all_gather(x, axis, tiled=tiled)

    def axis_index(self, axis):
        return self._jax.lax.axis_index(axis)
