"""Time the ways of ranking rows at the shapes the engine stages.

Two primitives sit behind `op.compact` and the generic join's probe
(`core/backend.py`):

- compaction: the row ids of a mask's first `cap` valid rows, in order,
  padded with n-1 (`JaxBackend.compact`);
- the probe: `searchsorted(a, v, side="left")` over a sorted build
  (`JaxBackend.searchsorted`).

The engine's own routes are timed as the engine stages them
(`backend._COMPACT`, `JaxBackend.searchsorted`), so the crossover that
`backend._SCATTER_AT` encodes is read off this table.  Besides them,
the loop-free candidates a later change of route would take: for
compaction `jnp.nonzero(size=cap)` (cumsum, a scatter-add histogram,
cumsum) and a stable argsort of the mask; for the probe
`jnp.searchsorted`'s `sort` and `scan_unrolled` methods.  These compile
for tens of seconds at these lengths, so they are timed at three shapes
only (`SLOW_SHAPES`).

Each candidate is jitted alone over device-made inputs, warmed, checked
against the engine's search, and timed (median of `--reps` runs, each
ended by `block_until_ready`).  One JSON line a measurement, and all of
them in `--out` (default `BENCH_rank.json`):

    python3 scripts/bench_rank.py                       # the chip's shapes
    JAX_PLATFORMS=cpu python3 scripts/bench_rank.py --div 64  # CPU, small

`--div` divides every length, so the CPU finishes in a minute or so.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import backend as be

I32MAX = 2**31 - 1


def c_nonzero(mask, cap):
    idx = jnp.nonzero(mask, size=cap, fill_value=mask.shape[0] - 1)[0]
    return idx.astype(jnp.int32), jnp.sum(mask, dtype=jnp.int32)


def c_sort(mask, cap):
    n = mask.shape[0]
    _, order = lax.sort((~mask, jnp.arange(n, dtype=jnp.int32)),
                        num_keys=1, is_stable=True)
    count = jnp.sum(mask, dtype=jnp.int32)
    idx = jnp.where(jnp.arange(cap) < count, order[:cap], n - 1)
    return idx.astype(jnp.int32), count


COMPACT = dict(be._COMPACT, nonzero=c_nonzero, sort=c_sort)

PROBE = {"search": be.JaxBackend().searchsorted}
PROBE.update({m: (lambda a, v, m=m: jnp.searchsorted(a, v, method=m))
              for m in ("sort", "scan_unrolled")})

# q3's compaction (24M rows, 4M slots), a per-shard point (6M, 1M) and
# q18's probe (24M build, 1.5M keys)
SLOW = {"nonzero", "sort", "scan_unrolled"}
SLOW_SHAPES = {(24_000_000, 4 << 20), (6_000_000, 1 << 20),
               (24_000_000, 1_500_000)}


def timed(f, args, reps):
    t0 = time.perf_counter()
    c = jax.jit(f).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = c(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(c(*args))
        ts.append(time.perf_counter() - t0)
    return out, statistics.median(ts) * 1e3, compile_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--div", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="BENCH_rank.json")
    args = ap.parse_args()
    div = args.div
    M = 1 << 20
    rows = []
    t_start = time.perf_counter()

    def emit(**r):
        r["platform"] = jax.devices()[0].platform
        r["device_kind"] = jax.devices()[0].device_kind
        print(json.dumps(r), flush=True)
        rows.append(r)

    def wanted(route, shape):
        return route not in SLOW or shape in SLOW_SHAPES

    key = jax.random.key(0)
    for n0, caps in ((24_000_000, (64 << 10, 512 << 10, M, 2 * M, 4 * M)),
                     (6_000_000, (4096, 64 << 10, 512 << 10, M, 2 * M)),
                     (1_500_000, (4096, 64 << 10, 512 << 10, M))):
        for cap0 in caps:
            n, cap = n0 // div, max(cap0 // div, 1)
            k = jax.random.fold_in(key, n * 7 + cap)
            # half the slots filled, as a planned capacity is
            mask = jax.random.uniform(k, (n,)) < 0.5 * cap / n
            ref = None
            for route, f in COMPACT.items():
                if not wanted(route, (n0, cap0)):
                    continue
                out, ms, cs = timed(lambda m, f=f: f(m, cap), (mask,),
                                    args.reps)
                same = None
                if ref is None:
                    ref = out
                else:
                    same = bool(jnp.array_equal(out[0], ref[0])
                                & (out[1] == ref[1]))
                emit(kind="compact", n=n, cap=cap, route=route,
                     gathers_a_row=round(cap * be._levels(n) / n, 3),
                     ms=ms, compile_s=cs, same=same)
    # the probe: sorted build with a masked (sentinel) tail, absent keys
    for na0 in (25, 64 << 10, 1_500_000, 6_000_000, 24_000_000):
        for nv0 in (1_500_000, 6_000_000):
            na, nv = max(na0 // div, 25), nv0 // div
            k = jax.random.fold_in(key, na * 13 + nv)
            a = jnp.sort(jax.random.randint(k, (na,), 0, 4 * na))
            a = jnp.where(jnp.arange(na) < na - na // 10, a, I32MAX)
            v = jax.random.randint(jax.random.fold_in(k, 1), (nv,), 0,
                                   4 * na)
            ref = None
            for method, f in PROBE.items():
                if not wanted(method, (na0, nv0)):
                    continue
                out, ms, cs = timed(f, (a, v), args.reps)
                same = None if ref is None else bool(jnp.array_equal(out,
                                                                     ref))
                ref = out if ref is None else ref
                emit(kind="probe", na=na, nv=nv, route=method, ms=ms,
                     compile_s=cs, same=same)
    emit(kind="total", s=time.perf_counter() - t_start)
    with open(args.out, "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
