"""Backend shim: the staging walker runs twice — once eagerly on numpy
(8-row samples, to collect the input set and exercise static decisions) and
once under jax tracing (the real staged program).  This shim abstracts the
handful of ops whose spelling differs."""
from __future__ import annotations

import contextlib
import math

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

    @contextlib.contextmanager
    def _rank(self, route: str):
        """Stage one rank site: `op.rank` is the scope the trace's
        per-operator split reads, `rank.<route>` names the route (and is
        what `CompiledQuery.rank_routes` counts)."""
        with self._jax.named_scope("op.rank"), \
                self._jax.named_scope(f"rank.{route}"):
            yield

    def compact(self, mask, capacity):
        """(idx int32[capacity], count int32), as `NumpyBackend.compact`
        but padded with n-1: the row ids of the mask's first `capacity`
        valid rows, in order.  `count > capacity` is the caller's overflow
        flag; the caller's pad mask (`arange(capacity) < count`) hides the
        pad slots.  The route comes from the static shapes
        (`compact_route`); every route gives the same idx and count and
        vmaps like any other op."""
        route = compact_route(mask.shape[0], capacity)
        with self._rank(route):
            return _COMPACT[route](mask, capacity)

    def barrier(self, x):
        import jax

        return jax.lax.optimization_barrier(x)

    def searchsorted(self, a, v):
        """`np.searchsorted(a, v, side="left")` over a sorted `a`: the
        binary search, staged as a rank site (see `compact_route`)."""
        with self._rank("search"):
            return self.xp.searchsorted(a, v)

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


# -- rank routes ---------------------------------------------------------------
# Compaction and the generic join's probe both find positions in a sorted
# sequence: compaction the first row whose running valid count reaches
# j+1, for each output slot j; the probe each stream key's left insertion
# point in the sorted build.  A binary search (`jnp.searchsorted`'s scan)
# does either as ceil(log2(n+1)) dependent gathers over every query: on a
# TPU an XLA `while` loop whose levels each gather the whole query vector.
# Compaction has a loop-free route that pays once for every row of the
# frame instead: a scatter of each row id to its running count.
# `compact_route` picks the cheaper from the static lengths.  The probe
# keeps the search: a loop-free probe has to sort the stream keys with
# the build's, and one such sort compiled for 20 s on a v5e (24M build
# keys, 1.5M stream keys), against 0.3 s for the search.

# route "scatter" wins once the search's gathers outnumber the frame's
# rows this many times: capacity * levels(n) > _SCATTER_AT * n.  One
# value for every platform, timed on a TPU v5e (`scripts/bench_rank.py`):
# a sorted scatter-min costs about 9 ns a row at every capacity (218 ms
# over 24M rows, 55 over 6M, 15 over 1.5M), a search gather 7-9 ns, and
# the two cross at 1.19 gathers a row over 24M rows, 1.23 over 6M and
# 1.5M (at 4M slots over 24M rows: search 912 ms, scatter 218).
_SCATTER_AT = 1.2


def _levels(n: int) -> int:
    """Dependent gathers of a binary search over n sorted values."""
    return max(math.ceil(math.log2(n + 1)), 1)


def compact_route(n: int, capacity: int) -> str:
    """'search' or 'scatter', for a frame of n rows compacted to
    `capacity` slots."""
    return "scatter" if capacity * _levels(n) > _SCATTER_AT * n \
        else "search"


def _compact_search(mask, capacity):
    # running count, then the first row reaching j+1 for each slot j;
    # slots past the count search off the end and clamp to n-1
    import jax.numpy as jnp

    c = jnp.cumsum(mask.astype(jnp.int32))
    idx = jnp.searchsorted(c, jnp.arange(1, capacity + 1, dtype=jnp.int32))
    return jnp.clip(idx, 0, mask.shape[0] - 1).astype(jnp.int32), c[-1]


def _compact_scatter(mask, capacity):
    # slot j is the least row whose running count is j+1: that row is the
    # (j+1)-th valid one, and the invalid rows after it share its count.
    # The counts never fall, so one scatter-min with sorted indices finds
    # every slot (an unsorted scatter would have XLA sort the indices
    # first); slot 0 of the buffer takes the rows before the first valid
    # one, and counts past the capacity drop
    import jax.numpy as jnp

    n = mask.shape[0]
    c = jnp.cumsum(mask.astype(jnp.int32))
    first = jnp.full((capacity + 1,), n - 1, jnp.int32).at[c].min(
        jnp.arange(n, dtype=jnp.int32), mode="drop", indices_are_sorted=True)
    return first[1:], c[-1]


_COMPACT = {"search": _compact_search, "scatter": _compact_scatter}
# the one equation a route stages at each rank site: the search's loop
# (`jnp.searchsorted`'s scan) and the scatter-min
RANK_OP = {"search": "scan", "scatter": "scatter-min"}
