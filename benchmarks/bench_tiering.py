"""Execution tiers end-to-end: instant cold serving, background promotion,
warm-state persistence (docs/architecture.md §11).

Three claims, each measured and gated:

  1. cold serving — request 1 on a stone-cold tiered `PlanCache` is
     answered by the oracle tier at interpreter cost, NOT the multi-second
     staging+XLA compile a blocking cache charges its first caller.  Gate:
     first-request latency <= 10x the bare Volcano execution of the same
     plan (the oracle serve plus cache bookkeeping).
  2. background promotion — while the oracle serves, the promoter
     compiles the target tier and hot-swaps it in; results are
     bit-comparable to the Volcano oracle at EVERY tier (zero drift), and
     steady-state latency after the swap is the compiled tier's.
  3. warm restart — a converged cache (compaction feedback, capacity
     overrides) persisted with `PlanCache.save` and restored into a fresh
     process-stand-in serves request 1 at the pre-restart converged
     capacities: same capacity signature, zero overflows, no
     re-convergence.  The JAX persistent compilation cache is wired so
     the XLA executable itself is also reused across the restart.

Writes `BENCH_tiering.json` (or $REPRO_BENCH_TIERING_OUT).
Scale factor: REPRO_TIERING_SF, default 0.01 (serving-sized).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

from repro.core import PlanCache, VolcanoEngine, preset
from repro.core import compile as compile_mod
from repro.core.persist import enable_compilation_cache
from repro.relational import Database
from repro.relational.queries import PARAM_QUERIES
from repro.relational.schema import days

from benchmarks.bench_compaction import _drift
from benchmarks.common import REPEATS

SF = float(os.environ.get("REPRO_TIERING_SF", "0.01"))
COLD_QUERIES = ["q1", "q6", "q12"]
COLD_RATIO_GATE = 10.0

# initial selective binding -> steady binding, as in
# bench_adaptive_compaction: drives the feedback loop so the warm-restart
# section has converged capacity overrides worth persisting
WARM_SCHEDULES = {
    "q3": {"cutoff": days("1998-11-01")},
    "q12": {"receipt_lo": days("1994-01-01"),
            "receipt_hi": days("1994-02-01")},
}
STEADY_RUNS = 8


def _min_time(fn, n) -> float:
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _bench_cold(database, oracle, settings, out) -> dict:
    section = {}
    for qname in COLD_QUERIES:
        build, defaults = PARAM_QUERIES[qname]
        # one-shot oracle cost: the fair baseline for a one-shot first
        # request (min-of-repeats is also recorded, but warm-loop timings
        # flatter the interpreter and would make the 10x gate jittery)
        t0 = time.perf_counter()
        oracle.execute(build(), defaults)
        oracle_s = time.perf_counter() - t0
        oracle_min_s = min(oracle_s, _min_time(
            lambda: oracle.execute(build(), defaults),
            max(2, REPEATS // 2)))

        cache = PlanCache(database, tiered=True)
        try:
            before = compile_mod.STAGINGS
            t0 = time.perf_counter()
            res1, tier1 = cache.execute_tiered(build(), settings, defaults)
            first_s = time.perf_counter() - t0
            stagings_inline = compile_mod.STAGINGS - before
            drift1 = _drift(res1, oracle.execute(build(), defaults))

            # requests until the hot swap lands (the promoter races real
            # traffic here, so this is a measurement, not a constant)
            promoted_after = 1 if tier1 != "oracle" else None
            for i in range(2, 65):
                if promoted_after is not None:
                    break
                _, t = cache.execute_tiered(build(), settings, defaults)
                if t != "oracle":
                    promoted_after = i
            cache.await_promotion(build(), settings, defaults, timeout=600)
            res_hot, tier_hot = cache.execute_tiered(build(), settings,
                                                     defaults)
            drift_hot = _drift(res_hot, oracle.execute(build(), defaults))
            hot_s = _min_time(
                lambda: cache.execute_tiered(build(), settings, defaults),
                max(3, REPEATS))

            # contrast: what request 1 costs when the first caller must
            # block on the full compile (fresh non-tiered cache)
            blocking = PlanCache(database)
            t0 = time.perf_counter()
            blocking.execute(build(), settings, defaults)
            blocking_cold_s = time.perf_counter() - t0

            section[qname] = {
                "oracle_s": oracle_s,
                "oracle_min_s": oracle_min_s,
                "first_request_s": first_s,
                "first_request_tier": tier1,
                "first_vs_oracle": first_s / max(oracle_s, 1e-9),
                "inline_stagings_on_request_1": stagings_inline,
                "blocking_cold_s": blocking_cold_s,
                "cold_speedup_vs_blocking":
                    blocking_cold_s / max(first_s, 1e-9),
                "requests_until_promoted": promoted_after,
                "steady_tier": tier_hot,
                "steady_s": hot_s,
                "promotions": cache.stats.promotions,
                "promote_failures": cache.stats.promote_failures,
                "tier_hits": dict(cache.stats.tier_hits),
                "max_rel_drift_vs_oracle": max(drift1, drift_hot),
            }
            out(f"tiering/{qname}/first_request,{first_s * 1e6:.1f},"
                f"{section[qname]['first_vs_oracle']:.2f}x oracle on "
                f"tier {tier1}")
            out(f"tiering/{qname}/blocking_cold,{blocking_cold_s * 1e6:.1f},"
                f"{section[qname]['cold_speedup_vs_blocking']:.1f}x slower "
                "than tiered request 1")
            out(f"tiering/{qname}/steady,{hot_s * 1e6:.1f},"
                f"tier {tier_hot} after "
                f"{promoted_after} request(s)")
        finally:
            cache.close()
    return section


def _converge(cache, settings, build, initial, steady) -> dict:
    cache.execute(build(), settings, initial)
    for _ in range(STEADY_RUNS):
        cache.execute(build(), settings, steady)
    cq, _ = cache.get(build(), settings, steady)
    return {"capacities": list(cq.capacities),
            "replans": cache.stats.replans,
            "overflows": cache.stats.overflows}


def _bench_warm_restart(database, settings, out, workdir) -> dict:
    section = {"jax_compilation_cache_dir": enable_compilation_cache()}
    for qname, init_overlay in WARM_SCHEDULES.items():
        build, defaults = PARAM_QUERIES[qname]
        initial = dict(defaults, **init_overlay)
        path = os.path.join(workdir, f"warm-{qname}.json")

        cache = PlanCache(database)
        pre = _converge(cache, settings, build, initial, defaults)
        saved = cache.save(path)

        # "restart": a fresh cache over the same data restores the
        # feedback store; its FIRST compile must plan at the converged
        # capacities and request 1 must not overflow
        restored_cache = PlanCache(database)
        n_restored = restored_cache.load(path)
        t0 = time.perf_counter()
        restored_cache.execute(build(), settings, defaults)
        first_s = time.perf_counter() - t0
        cq, _ = restored_cache.get(build(), settings, defaults)

        # a cold control: same fresh-cache first request WITHOUT the
        # restored state plans at the sketch estimate instead
        control = PlanCache(database)
        control.execute(build(), settings, defaults)
        ctrl_cq, _ = control.get(build(), settings, defaults)

        section[qname] = {
            "records_saved": saved,
            "records_restored": n_restored,
            "warm_hint": restored_cache.is_warm(build(), settings, defaults),
            "pre_restart_capacities": pre["capacities"],
            "pre_restart_replans": pre["replans"],
            "restored_first_request_s": first_s,
            "restored_capacities": list(cq.capacities),
            "capacities_match": list(cq.capacities) == pre["capacities"],
            "restored_first_overflows": cq.n_overflows,
            "cold_control_capacities": list(ctrl_cq.capacities),
        }
        out(f"tiering/restart/{qname},{first_s * 1e6:.1f},"
            f"caps {pre['capacities']} restored="
            f"{section[qname]['capacities_match']} "
            f"overflows={cq.n_overflows}")
    return section


def run(out=print) -> dict:
    database = Database.tpch(sf=SF, seed=0)
    oracle = VolcanoEngine(database)
    settings = preset("opt")
    results: dict = {"sf": SF}
    with tempfile.TemporaryDirectory(prefix="bench-tiering-") as workdir:
        results["cold_serving"] = _bench_cold(database, oracle, settings,
                                              out)
        results["warm_restart"] = _bench_warm_restart(database, settings,
                                                      out, workdir)

    cold = results["cold_serving"].values()
    warm = [v for k, v in results["warm_restart"].items()
            if isinstance(v, dict)]
    results["summary"] = {
        "max_first_vs_oracle": max(c["first_vs_oracle"] for c in cold),
        "cold_ratio_gate": COLD_RATIO_GATE,
        "all_promoted": all(c["steady_tier"] != "oracle" for c in cold),
        "max_drift": max(c["max_rel_drift_vs_oracle"] for c in cold),
        "inline_stagings_on_cold_requests":
            sum(c["inline_stagings_on_request_1"] for c in cold),
        "all_capacities_restored": all(w["capacities_match"] for w in warm),
        "restored_first_overflows":
            sum(w["restored_first_overflows"] for w in warm),
    }
    path = os.environ.get("REPRO_BENCH_TIERING_OUT", "BENCH_tiering.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    out(f"wrote {path}")
    return results


if __name__ == "__main__":
    res = run()
    s = res["summary"]
    # hard gates, mirroring the issue's acceptance criteria; raw latencies
    # stay advisory (recorded in the JSON) since CI runners vary
    ok = (s["max_first_vs_oracle"] <= s["cold_ratio_gate"]
          and s["all_promoted"]
          and s["max_drift"] < 1e-2
          and s["all_capacities_restored"]
          and s["restored_first_overflows"] == 0)
    sys.exit(0 if ok else 1)
