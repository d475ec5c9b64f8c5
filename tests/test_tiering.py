"""Execution tiers (docs §11): ladder, tiered cache, persistence, server.

Covers the four layers the tier abstraction spans:

  * the ladder itself — target derivation, per-tier settings (the
    interpret rung must be *exactly* the server's historical
    `pipeline.degrade`), demotion clamping, promotion paths;
  * the Runnable contract — `OracleQuery` is substitutable for
    `CompiledQuery` (same binding validation, same results, run and
    run_many);
  * the tiered PlanCache — a cold request is served by the oracle with
    ZERO staging, a background promotion hot-swaps the target tier in
    with zero result drift, promotion is deduplicated, and a typed
    compile failure falls back to the ready tier (sticky, no retry
    storm);
  * warm-state persistence — save/load round-trips the compaction
    feedback store and warm hints keyed by content fingerprint; a
    corrupt or mismatched file is a cold start, never a crash.
"""
import dataclasses
import os
import threading

import numpy as np
import pytest

from repro.core import compile as compile_mod
from repro.core import tiering
from repro.core.plan_cache import PlanCache
from repro.core.tiering import (COMPILED, INTERPRET, OPT_PALLAS, ORACLE,
                                Runnable, TierLadder)
from repro.core.volcano import OracleQuery, VolcanoEngine
from repro.core.passes.pipeline import degrade, preset
from repro.relational.queries import (PARAM_ALT_BINDINGS, PARAM_QUERIES,
                                      QUERIES)
from repro.relational.schema import days
from repro.serve.query_server import QueryServer
from tests.test_queries import assert_same

OPT = preset("opt")


# -- the ladder --------------------------------------------------------------

def test_ladder_target_derivation():
    assert TierLadder(OPT).target is COMPILED
    assert TierLadder(dataclasses.replace(OPT, use_pallas=True)).target \
        is OPT_PALLAS
    assert TierLadder(dataclasses.replace(OPT, engine="volcano")).target \
        is ORACLE


def test_ladder_interpret_is_exactly_degrade():
    # the server's shed-plan rung and the cache's interpret tier must be
    # the same settings object value, or the two subsystems would key
    # different plan-cache entries for the same rung
    lad = TierLadder(OPT)
    assert lad.settings_for(INTERPRET) == degrade(OPT)


def test_ladder_settings_preserve_semantics():
    lad = TierLadder(dataclasses.replace(OPT, use_pallas=True))
    assert lad.settings_for(COMPILED).use_pallas is False
    assert lad.settings_for(ORACLE).engine == "volcano"
    with pytest.raises(ValueError):
        TierLadder(OPT).settings_for(OPT_PALLAS)


def test_ladder_demote_clamps():
    lad = TierLadder(OPT)
    assert lad.demote(COMPILED) is INTERPRET
    assert lad.demote(COMPILED, 2) is ORACLE
    assert lad.demote(ORACLE, 5) is ORACLE


def test_promotion_path():
    lad = TierLadder(OPT)
    assert lad.promotion_path(ORACLE) == [COMPILED]
    assert lad.promotion_path(ORACLE, through=True) == [INTERPRET, COMPILED]
    assert lad.promotion_path(COMPILED) == []
    assert tiering.tier("oracle") is ORACLE
    with pytest.raises(KeyError):
        tiering.tier("warp-speed")


# -- the Runnable contract ---------------------------------------------------

def test_oracle_query_satisfies_runnable(db):
    fn, defaults = PARAM_QUERIES["q6"]
    oq = OracleQuery(fn(), db, params=defaults)
    assert isinstance(oq, Runnable)
    assert oq.tier_name == "oracle"
    assert oq.compaction_points == 0 and oq.n_overflows == 0


def test_oracle_query_matches_compiled(db):
    fn, defaults = PARAM_QUERIES["q6"]
    alt = dict(defaults, **PARAM_ALT_BINDINGS["q6"])
    oq = OracleQuery(fn(), db, params=defaults)
    from repro.core import CompiledQuery
    cq = CompiledQuery(fn(), db, OPT, params=defaults)
    assert_same(oq.run(defaults), cq.run(defaults), False)
    for a, b in zip(oq.run_many([defaults, alt]),
                    cq.run_many([defaults, alt])):
        assert_same(a, b, False)
    assert oq.n_executions == 3


def test_oracle_query_binding_validation(db):
    fn, defaults = PARAM_QUERIES["q6"]
    oq = OracleQuery(fn(), db, params=defaults)
    with pytest.raises(KeyError):
        oq.run({"date_lo": 1})          # missing params
    with pytest.raises(KeyError):
        oq.run(dict(defaults, bogus=1))  # unknown param
    plain = OracleQuery(QUERIES["q6"](), db)
    assert plain.param_spec == {}
    assert plain.run() is not None


# -- the tiered cache --------------------------------------------------------

def q6_req():
    fn, defaults = PARAM_QUERIES["q6"]
    return fn(), defaults


def test_cold_serve_is_oracle_with_zero_staging(db):
    cache = PlanCache(db, tiered=True)
    try:
        plan, defaults = q6_req()
        key, prepared, runtime, owned = cache._prepare(plan, OPT, defaults, "residual")
        gate = threading.Event()   # holds the promoter at the door so the
        #                            cold read is deterministic
        before = compile_mod.STAGINGS
        run, _, tier_name = cache._get_tiered_prepared(
            key, prepared, runtime, owned, OPT,
            compile_hook=lambda k: gate.wait(60))
        # the caller's thread never staged anything: request 1 is served
        # before the target tier exists
        assert tier_name == "oracle"
        assert isinstance(run, OracleQuery)
        assert compile_mod.STAGINGS == before
        assert cache.stats.tier_hits == {"oracle": 1}
        assert cache.stats.misses == 1
        gate.set()
        assert cache.await_promotion(plan, OPT, defaults, timeout=120)
        assert cache.stats.promote_failures == 0
    finally:
        cache.close()


def test_promotion_hot_swaps_with_zero_drift(db):
    cache = PlanCache(db, tiered=True)
    try:
        plan, defaults = q6_req()
        key, prepared, runtime, owned = cache._prepare(plan, OPT, defaults, "residual")
        gate = threading.Event()
        run1, _, tier1 = cache._get_tiered_prepared(
            key, prepared, runtime, owned, OPT,
            compile_hook=lambda k: gate.wait(60))
        assert tier1 == "oracle"
        res1 = run1.run(runtime)
        gate.set()
        assert cache.await_promotion(plan, OPT, defaults, timeout=120)
        res2, tier2 = cache.execute_tiered(plan, OPT, defaults)
        assert tier2 == "compiled"
        oracle = VolcanoEngine(db).execute(q6_req()[0], defaults)
        assert_same(res1, oracle, False)
        assert_same(res2, oracle, False)
        assert cache.stats.promotions == 1
        assert cache.stats.promote_failures == 0
        # promoted entry is the canonical one: plain get() now hits
        cq, _ = cache.get(plan, OPT, defaults)
        assert cq.tier_name == "compiled"
    finally:
        cache.close()


def test_promotion_is_deduplicated(db):
    cache = PlanCache(db, tiered=True)
    try:
        plan, defaults = q6_req()
        for _ in range(8):
            _, _, tier_name = cache.get_tiered(plan, OPT, defaults)
        cache.await_promotion(plan, OPT, defaults, timeout=120)
        # eight requests raced the single promotion; exactly one compile
        assert cache.stats.compiles == 1
        assert cache.stats.misses == 1
        assert cache.stats.hits >= 7
        assert cache.stats.promote_failures == 0
    finally:
        cache.close()


def test_promote_through_builds_interpret_rung(db):
    cache = PlanCache(db, tiered=True, promote_through=True)
    try:
        plan, defaults = q6_req()
        cache.get_tiered(plan, OPT, defaults)
        assert cache.await_promotion(plan, OPT, defaults, timeout=240)
        # two rungs landed: interpret then compiled
        assert cache.stats.promotions == 2
        assert cache.stats.compiles == 2
        assert cache.stats.promote_failures == 0
    finally:
        cache.close()


def test_promotion_failure_falls_back_sticky(db):
    cache = PlanCache(db, tiered=True)
    try:
        plan, defaults = q6_req()
        key, prepared, runtime, owned = cache._prepare(plan, OPT, defaults, "residual")
        calls = []

        def boom(k):
            calls.append(k)
            raise RuntimeError("injected compile fault")

        run, _, tier_name = cache._get_tiered_prepared(
            key, prepared, runtime, owned, OPT, compile_hook=boom)
        assert tier_name == "oracle"
        assert not cache.await_promotion(plan, OPT, defaults, timeout=60)
        assert cache.stats.promote_failures == 1
        # the ready tier keeps serving, and the failure is sticky — no
        # promotion retry storm on subsequent requests
        for _ in range(3):
            _, _, t = cache._get_tiered_prepared(
                key, prepared, runtime, owned, OPT, compile_hook=boom)
            assert t == "oracle"
        assert len(calls) == 1
        assert cache.stats.promote_failures == 1
    finally:
        cache.close()


def test_oracle_target_ladder_degenerates(db):
    cache = PlanCache(db, tiered=True)
    try:
        volcano = dataclasses.replace(OPT, engine="volcano")
        plan, defaults = q6_req()
        _, _, tier_name = cache.get_tiered(plan, volcano, defaults)
        assert tier_name == "oracle"
        # nothing to promote toward; await resolves immediately as False
        assert not cache.await_promotion(plan, volcano, defaults, timeout=5)
        assert cache.stats.promotions == 0
    finally:
        cache.close()


# -- persistence -------------------------------------------------------------

def test_warm_state_round_trip(db, tmp_path):
    path = str(tmp_path / "warm.json")
    cache = PlanCache(db)
    plan, defaults = q6_req()
    cache.execute(plan, OPT, defaults)
    # synthesize a converged feedback record: persisted overrides must
    # drive the restored cache's first compile capacities
    base = cache.key_for(plan, OPT, defaults)[:-1]
    fb = cache._feedback[base]
    overrides = {pid: int(v) + 32 for pid, v in fb.est_params.items()
                 if isinstance(v, (int, np.integer))}
    fb.overrides = dict(overrides) or {"p0": 64}
    fb.replans = 2
    assert cache.save(path) >= 1

    fresh = PlanCache(db)
    assert fresh.load(path) >= 1
    assert fresh.stats.restored >= 1
    assert fresh.is_warm(plan, OPT, defaults)
    rec = fresh._feedback[fresh.key_for(plan, OPT, defaults)[:-1]]
    assert rec.overrides == fb.overrides
    assert rec.replans == 2
    # live observations beat stale disk: loading twice doesn't clobber
    assert fresh.load(path) == 0


# initial selective binding overlay per template: the default binding that
# follows overflows the first plan's capacities, so the feedback loop
# converges on overrides worth persisting
WARM_SCHEDULES = {
    "q3": {"cutoff": days("1998-11-01")},
    "q12": {"receipt_lo": days("1994-01-01"),
            "receipt_hi": days("1994-02-01")},
}


@pytest.mark.parametrize("qname", sorted(WARM_SCHEDULES))
def test_warm_restart_plans_at_converged_capacities(db, tmp_path, qname):
    """A converged cache saved and loaded into a fresh one: the fresh
    cache's first compile uses the converged capacities and its first
    request does not overflow."""
    build, defaults = PARAM_QUERIES[qname]
    path = str(tmp_path / "warm.json")
    cache = PlanCache(db)
    cache.execute(build(), OPT, dict(defaults, **WARM_SCHEDULES[qname]))
    for _ in range(8):
        cache.execute(build(), OPT, defaults)
    converged, _ = cache.get(build(), OPT, defaults)
    assert converged.compaction_points
    assert cache.save(path) >= 1

    fresh = PlanCache(db)
    assert fresh.load(path) >= 1
    assert_same(fresh.execute(build(), OPT, defaults),
                VolcanoEngine(db).execute(build(), defaults),
                sort_insensitive=True)
    cq, _ = fresh.get(build(), OPT, defaults)
    assert list(cq.capacities) == list(converged.capacities)
    assert cq.n_overflows == 0


def test_corrupt_or_mismatched_warm_state_is_cold_start(db, tmp_path):
    cache = PlanCache(db)
    missing = str(tmp_path / "nope.json")
    assert cache.load(missing) == 0
    truncated = tmp_path / "warm.json"
    truncated.write_text('{"version": 1, "db": "x", "feedback": [{')
    assert cache.load(str(truncated)) == 0
    truncated.write_text('{"version": 99, "db": "x", "feedback": []}')
    assert cache.load(str(truncated)) == 0
    truncated.write_text('{"version": 1, "db": "other", "feedback": []}')
    assert cache.load(str(truncated)) == 0
    assert cache.stats.restored == 0


def test_save_is_atomic_and_versioned(db, tmp_path):
    import json
    path = str(tmp_path / "warm.json")
    cache = PlanCache(db)
    plan, defaults = q6_req()
    cache.execute(plan, OPT, defaults)
    cache.save(path)
    payload = json.loads(open(path).read())
    assert payload["version"] == 1
    assert payload["db"] == db.content_fingerprint()
    assert payload["feedback"][0]["warm"] is True
    assert not [p for p in os.listdir(str(tmp_path))
                if p.startswith(".warm-state-")]


def test_content_fingerprint_stability(db):
    # process-restart stand-in: same data -> same fingerprint; the
    # process-local monotonic fingerprint is NOT what's persisted
    assert db.content_fingerprint() == db.content_fingerprint()
    from repro.relational.loader import Database
    other = Database.tpch(sf=0.01, seed=1)
    assert other.content_fingerprint() != db.content_fingerprint()


# -- the tiered server -------------------------------------------------------

def test_server_ladder_parity(db):
    with QueryServer(db, OPT) as srv:
        # the degradation rung is the ladder's interpret tier — identical
        # to the historical degrade(settings) plan key
        assert srv._degraded_settings == degrade(OPT)
        assert srv.ladder.target is COMPILED


def test_tiered_server_serves_cold_then_promotes(db, tmp_path):
    path = str(tmp_path / "server-warm.json")
    plan_fn, defaults = PARAM_QUERIES["q6"]
    oracle_res = VolcanoEngine(db).execute(plan_fn(), defaults)

    gate = threading.Event()   # deterministic: request 1 beats promotion
    srv = QueryServer(db, OPT, tiered=True, warm_state_path=path,
                      compile_hook=lambda k: gate.wait(60))
    try:
        res1 = srv.submit(plan_fn(), defaults).result(timeout=120)
        assert_same(res1, oracle_res, False)
        assert srv.stats.tier_served.get("oracle", 0) >= 1
        gate.set()
        srv.cache.await_promotion(plan_fn(), OPT, defaults, timeout=120)
        res2 = srv.submit(plan_fn(), defaults).result(timeout=120)
        assert_same(res2, oracle_res, False)
        assert srv.stats.tier_served.get("compiled", 0) >= 1
        assert srv.cache.stats.promote_failures == 0
    finally:
        srv.close()
    assert os.path.exists(path)

    # restart: warm metadata restored, prewarm promotes without traffic
    srv2 = QueryServer(db, OPT, tiered=True, warm_state_path=path)
    try:
        assert srv2.cache.stats.restored >= 1
        assert srv2.prewarm([(plan_fn(), defaults)]) == 1
        assert srv2.cache.await_promotion(plan_fn(), OPT, defaults,
                                          timeout=120)
        res = srv2.submit(plan_fn(), defaults).result(timeout=120)
        assert_same(res, oracle_res, False)
        # request 1 after prewarm runs on the target tier, not the oracle
        assert srv2.stats.tier_served == {"compiled": 1}
        assert srv2.cache.stats.promote_failures == 0
    finally:
        srv2.close()


def test_tiered_cache_run_many_skips_pad_accounting(db):
    cache = PlanCache(db, tiered=True)
    try:
        plan, defaults = q6_req()
        key, prepared, runtime, owned = cache._prepare(plan, OPT, defaults, "residual")
        gate = threading.Event()
        run, runtime, _ = cache._get_tiered_prepared(
            key, prepared, runtime, owned, OPT,
            compile_hook=lambda k: gate.wait(60))
        gate.set()
        assert isinstance(run, OracleQuery)
        alt = dict(defaults, **PARAM_ALT_BINDINGS["q6"])
        results = cache.run_many(run, [runtime, alt, alt])
        assert len(results) == 3
        # the oracle executes bindings one by one: no pow2 bucket, no
        # padded-slot accounting (3 -> bucket 4 would charge 1)
        assert cache.stats.padded_slots == 0
        assert cache.await_promotion(plan, OPT, defaults, timeout=120)
        assert cache.stats.promote_failures == 0
    finally:
        cache.close()


def test_promoter_close_is_idempotent(db):
    cache = PlanCache(db, tiered=True)
    plan, defaults = q6_req()
    cache.get_tiered(plan, OPT, defaults)
    cache.close()
    cache.close()
    # a post-close request still serves the ready tier (promotion is
    # re-armed lazily; the pool was rebuilt or the ladder already done)
    _, _, tier_name = cache.get_tiered(plan, OPT, defaults)
    assert tier_name in ("oracle", "compiled")
    cache.close()
