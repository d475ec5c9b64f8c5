"""Open-loop serving sweep: throughput vs tail latency with and without
the overload machinery (admission + degradation ladder + adaptive
windows).

Two seeded arrival traces — Poisson (exponential gaps) and bursty
(on/off periods at 8x / x/8 the base rate) — are replayed open-loop
(arrival times fixed in advance, submission never waits for results,
the real overload regime) against two servers:

  * `degrading`: bounded budget, degradation ladder, adaptive window —
    the hardened configuration;
  * `plain`: effectively unbounded budget, fixed tick, no ladder — the
    pre-hardening server.

The scale factor defaults to 0.1 — large enough that per-request scan
compute dominates the dispatch (a vmapped batch of k costs ~k× a
scalar run), so service capacity is genuinely finite and an arrival
rate above it grows a real queue.  Arrival rates are multiples of the
measured batched capacity.  Above saturation the plain server's queue
(and therefore its p99) grows with the trace length, while the
degrading server holds p99 roughly flat by shedding and rejecting: the
`divergence` section replays the top rate at increasing N to show
exactly that.  Every completed result is checked against the Volcano
oracle — degradation must never cost correctness (`oracle_drift` must
be 0).

Writes `BENCH_serving.json` (or $REPRO_BENCH_SERVING_OUT).  Knobs:
REPRO_SERVE_SF (default 0.1), REPRO_SERVE_N (requests per trace,
default 240).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import VolcanoEngine, degrade, preset
from repro.core.plan_cache import PlanCache
from repro.relational import Database
from repro.relational.queries import PARAM_QUERIES
from repro.serve.query_server import QueryServer

SF = float(os.environ.get("REPRO_SERVE_SF", "0.1"))
N = int(os.environ.get("REPRO_SERVE_N", "240"))
MULTS = (0.25, 2.0, 8.0)          # arrival rate / batched service capacity
DIVERGE_NS = (N // 2, N)          # trace lengths for the divergence replay
MAX_BATCH = 8
WORKERS = 2
BUDGET = 32                       # degrading server's admission budget
N_BINDINGS = 8
SEED = 0


def _bindings_pool() -> list[dict]:
    _, defaults = PARAM_QUERIES["q6"]
    return [dict(defaults, qty_max=10.0 + 2.0 * i)
            for i in range(N_BINDINGS)]


def _arrivals(kind: str, n: int, rate: float, rng) -> np.ndarray:
    """Cumulative arrival offsets (seconds) for an open-loop trace."""
    if kind == "poisson":
        gaps = rng.exponential(1.0 / rate, size=n)
    else:                          # bursty: alternating 8x / x/8 periods
        period = max(n // 8, 1)
        on = (np.arange(n) // period) % 2 == 0
        gaps = np.where(on, rng.exponential(1.0 / (8 * rate), size=n),
                        rng.exponential(8.0 / rate, size=n))
    return np.cumsum(gaps)


def _make_server(db, cache: PlanCache, hardened: bool) -> QueryServer:
    if hardened:
        return QueryServer(db, preset("opt"), cache=cache,
                           max_batch=MAX_BATCH, max_workers=WORKERS,
                           window_s=0.002, budget=BUDGET,
                           degradation=True, adaptive_window=True,
                           shed_batch_load=0.7, shed_plan_load=0.85)
    return QueryServer(db, preset("opt"), cache=cache,
                       max_batch=MAX_BATCH, max_workers=WORKERS,
                       window_s=0.002, budget=1 << 30, degradation=False,
                       adaptive_window=False)


def _warm(cache: PlanCache, pool: list[dict]) -> None:
    """Pay every compile/trace outside the timed traces: the scalar + the
    vmapped buckets for the full settings, and the degraded (mask-only)
    twin the ladder switches to under load.  One shared cache serves all
    the trace servers, so this runs once."""
    build, _ = PARAM_QUERIES["q6"]
    for settings in (preset("opt"), degrade(preset("opt"))):
        cq, runtime = cache.get(build(), settings, pool[0])
        cq.run(runtime)
        for bsz in (2, 4, MAX_BATCH):
            runtimes = [dict(runtime) for _ in range(bsz)]
            cache.run_many(cq, runtimes)


def _trace(db, cache: PlanCache, hardened: bool, kind: str, rate: float,
           n: int, pool: list[dict], want: list[dict]) -> dict:
    build, _ = PARAM_QUERIES["q6"]
    rng = np.random.default_rng(SEED)
    offsets = _arrivals(kind, n, rate, rng)
    binding_ix = rng.integers(0, len(pool), size=n)
    srv = _make_server(db, cache, hardened)
    degraded_before = cache.stats.degraded
    lat: list[float] = []
    drift = [0]

    def on_done(i: int, t_arrival: float):
        def _cb(f):
            if f.cancelled() or f.exception() is not None:
                return
            lat.append(time.monotonic() - t_arrival)
            got = f.result()
            w = want[binding_ix[i]]
            same = set(got) == set(w) and all(
                np.allclose(np.asarray(got[c], np.float64),
                            np.asarray(w[c], np.float64),
                            rtol=1e-4, atol=1e-4) for c in got)
            if not same:
                drift[0] += 1
        return _cb

    rejected = 0
    t0 = time.monotonic()
    for i in range(n):
        due = t0 + offsets[i]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t_arr = time.monotonic()
        try:
            fut = srv.submit(build(), dict(pool[binding_ix[i]]),
                             tenant=f"t{i % 4}")
        except RuntimeError:       # Overloaded: the ladder's last rung
            rejected += 1
            continue
        fut.add_done_callback(on_done(i, t_arr))
    srv.drain()
    wall = time.monotonic() - t0
    srv.close()
    st = srv.stats
    lat_arr = np.sort(np.asarray(lat)) if lat else np.zeros(1)
    return {
        "n": n, "rate_per_s": rate, "completed": st.completed,
        "rejected": rejected, "shed_batch": st.shed_batch,
        "shed_plan": st.shed_plan, "deadline_misses": st.deadline_misses,
        "errors": st.errors, "retries": st.retries,
        "throughput_per_s": st.completed / wall if wall > 0 else 0.0,
        "p50_s": float(lat_arr[int(0.50 * (len(lat_arr) - 1))]),
        "p99_s": float(lat_arr[int(0.99 * (len(lat_arr) - 1))]),
        "oracle_drift": drift[0],
        "degraded_served": cache.stats.degraded - degraded_before,
    }


def run(out=print) -> dict:
    database = Database.tpch(sf=SF, seed=0)
    build, _ = PARAM_QUERIES["q6"]
    pool = _bindings_pool()
    oracle = VolcanoEngine(database)
    want = [oracle.execute(build(), b) for b in pool]

    cache = PlanCache(database)
    _warm(cache, pool)

    # measured batched capacity: the unit the arrival-rate sweep scales
    cq, runtime = cache.get(build(), preset("opt"), pool[0])
    runtimes = [dict(runtime) for _ in range(MAX_BATCH)]
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        cache.run_many(cq, runtimes)
        times.append(time.perf_counter() - t0)
    batch_s = min(times)
    # single-stream batched capacity (workers contend for the same
    # cores, so scaling by WORKERS would overestimate): x0.5 is real
    # underload, x2/x8 real overload
    base_rate = MAX_BATCH / batch_s
    out(f"serving/batch{MAX_BATCH}_time,{batch_s * 1e6:.1f},us")
    out(f"serving/capacity,{base_rate:.0f},req_per_s")

    results: dict = {"sf": SF, "n": N, "batch_s": batch_s,
                     "capacity_per_s": base_rate,
                     "traces": {}, "divergence": {}}
    total_drift = 0
    for kind in ("poisson", "bursty"):
        results["traces"][kind] = {}
        for m in MULTS:
            cell = {}
            for label, hardened in (("degrading", True), ("plain", False)):
                r = _trace(database, cache, hardened, kind, m * base_rate,
                           N, pool, want)
                cell[label] = r
                total_drift += r["oracle_drift"]
                out(f"serving/{kind}/x{m:g}/{label}/p99,"
                    f"{r['p99_s'] * 1e6:.1f},"
                    f"us thr={r['throughput_per_s']:.0f}/s "
                    f"rej={r['rejected']} shed={r['shed_batch']}"
                    f"+{r['shed_plan']}")
            results["traces"][kind][f"x{m:g}"] = cell

    # divergence: above saturation the plain p99 grows with trace length,
    # the degrading p99 must not
    top = max(MULTS)
    for n in DIVERGE_NS:
        cell = {}
        for label, hardened in (("degrading", True), ("plain", False)):
            r = _trace(database, cache, hardened, "poisson",
                       top * base_rate, n, pool, want)
            cell[label] = r
            total_drift += r["oracle_drift"]
            out(f"serving/diverge/n{n}/{label}/p99,"
                f"{r['p99_s'] * 1e6:.1f},us")
        results["divergence"][str(n)] = cell
    results["oracle_drift"] = total_drift

    path = os.environ.get("REPRO_BENCH_SERVING_OUT", "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    out(f"wrote {path}")
    return results


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
