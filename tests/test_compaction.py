"""Selection-vector compaction: oracle equivalence of compacted plans,
overflow-fallback correctness, the retrace bound (≤ one trace per capacity
bucket), and capacity wiring into the plan-cache key."""
import dataclasses

import numpy as np
import pytest

from repro.core import CompiledQuery, PlanCache, VolcanoEngine, preset
from repro.core import compile as compile_mod
from repro.core import ir
from repro.core.expr import Cmp, col, lit
from repro.core.ir import Agg, AggSpec, Compact, Scan, Select
from repro.core.passes.compaction import strip_compaction
from repro.relational.queries import (PARAM_ALT_BINDINGS as ALT_BINDINGS,
                                      PARAM_QUERIES, QUERIES)
from test_queries import assert_same

CONFIGS = ["naive", "template", "tpch", "strdict", "opt"]
# mirror test_queries: ladder endpoints always, interior rungs under -m slow
FAST_CONFIGS = ["naive", "opt"]
CONFIG_PARAMS = [
    pytest.param(c) if c in FAST_CONFIGS
    else pytest.param(c, marks=pytest.mark.slow)
    for c in CONFIGS
]
TARGETS = ["q3", "q6", "q19"]
# every query whose predicates leave a small fraction of a large frame
# alive (the q6/q19 class)
SELECTIVE = ["q3", "q5", "q6", "q7", "q10", "q12", "q17", "q19"]


def _compacted(settings):
    return dataclasses.replace(settings, compaction=True)


def _mask_only(settings):
    return dataclasses.replace(settings, compaction=False)


# ---------------------------------------------------------------------------
# oracle equivalence: compacted vs mask-only plans, every preset
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", CONFIG_PARAMS)
@pytest.mark.parametrize("qname", SELECTIVE)
def test_compacted_matches_mask_only_and_oracle(db, qname, config):
    want = VolcanoEngine(db).execute(QUERIES[qname]())
    on = CompiledQuery(QUERIES[qname](), db,
                       _compacted(preset(config))).run()
    off = CompiledQuery(QUERIES[qname](), db,
                        _mask_only(preset(config))).run()
    assert_same(on, want, sort_insensitive=True)
    assert_same(off, want, sort_insensitive=True)


@pytest.mark.parametrize("qname", TARGETS + ["q12"])
def test_compacted_param_variants_match_oracle(db, qname):
    """Param plans keep runtime predicates un-estimable (selectivity 1.0
    for Param bounds), but compile-time params and static conjuncts still
    plant points — both bindings must match the oracle."""
    build, defaults = PARAM_QUERIES[qname]
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)
    for bindings in (defaults, dict(defaults, **ALT_BINDINGS[qname])):
        got = cache.execute(build(), _compacted(preset("opt")), bindings)
        assert_same(got, oracle.execute(build(), bindings),
                    sort_insensitive=True)


def test_compaction_points_planted_on_selective_queries(db):
    """The pass must actually fire on the selective workload (capacities
    are power-of-two buckets strictly below the stream cardinality)."""
    planted = {}
    for qname in ("q3", "q5", "q7", "q10"):
        cq = CompiledQuery(QUERIES[qname](), db, preset("opt"))
        planted[qname] = cq.capacities
        assert cq.compaction_points == len(cq.capacities)
    assert any(planted.values()), f"no compaction anywhere: {planted}"
    n_li = db.table("lineitem").nrows
    for qname, caps in planted.items():
        for cap in caps:
            assert cap & (cap - 1) == 0, f"{qname}: {cap} not a pow2 bucket"
            assert cap < n_li


# ---------------------------------------------------------------------------
# overflow fallback
# ---------------------------------------------------------------------------

def _overflowing_plan():
    """Hand-planted Compact whose capacity is far below the surviving
    rows: every execution must overflow and fall back."""
    sel = Select(Scan("lineitem"), Cmp("<", col("l_quantity"), lit(26.0)))
    return Agg(Compact(sel, 64), [],
               [AggSpec("s", "sum", col("l_extendedprice")),
                AggSpec("c", "count")])


def test_overflow_falls_back_to_uncompacted_twin(db):
    want = VolcanoEngine(db).execute(_overflowing_plan())
    before = compile_mod.STAGINGS
    cq = CompiledQuery(_overflowing_plan(), db, preset("opt"))
    assert cq.compaction_points == 1
    r1 = cq.run()
    assert cq.n_overflows == 1
    # the fallback twin staged exactly once (plus the compacted program)
    assert compile_mod.STAGINGS - before == 2
    r2 = cq.run()
    assert cq.n_overflows == 2
    assert compile_mod.STAGINGS - before == 2, \
        "repeat overflows must reuse the compiled twin"
    assert_same(r1, want, sort_insensitive=False)
    assert_same(r2, want, sort_insensitive=False)


def test_overflow_fallback_in_batched_execution(db):
    """run_many with a hand-planted overflowing point: every slot falls
    back and still matches the scalar path."""
    build, defaults = PARAM_QUERIES["q6"]
    plan = build()
    # squeeze the q6 select through a 64-row bucket: defaults survive far
    # more rows than that, so all slots overflow
    assert isinstance(plan.child, Select)
    plan = Agg(Compact(plan.child, 64), [], plan.aggs)
    cq = CompiledQuery(plan, db, preset("opt"), params=defaults)
    bindings = [defaults, dict(defaults, qty_max=30.0), defaults]
    batched = cq.run_many(bindings)
    assert cq.n_overflows >= len(bindings)
    for got, b in zip(batched, [cq.run(b) for b in bindings]):
        for k in got:
            np.testing.assert_array_equal(got[k], b[k], err_msg=k)


def test_overflow_fallback_with_compaction_pass_disabled(db):
    """A hand-planted Compact can overflow even when the pass is off
    (e.g. a ladder preset); the fallback twin must still exist."""
    want = VolcanoEngine(db).execute(_overflowing_plan())
    cq = CompiledQuery(_overflowing_plan(), db, preset("naive"))
    assert cq.compaction_points == 1
    got = cq.run()
    assert cq.n_overflows == 1
    assert_same(got, want, sort_insensitive=False)


def test_strip_compaction_removes_every_point(db):
    plan = _overflowing_plan()
    stripped = strip_compaction(plan)
    assert not [n for n in ir.walk(stripped) if isinstance(n, Compact)]


def test_planner_capacities_do_not_overflow(db):
    """The margin + pow2 bucket must hold the actual surviving rows for
    the literal TPC-H workload (overflow would silently double latency)."""
    for qname in sorted(QUERIES):
        cq = CompiledQuery(QUERIES[qname](), db, preset("opt"))
        cq.run()
        assert cq.n_overflows == 0, \
            f"{qname} overflowed its planned capacities {cq.capacities}"


# ---------------------------------------------------------------------------
# retrace bound + plan-cache wiring
# ---------------------------------------------------------------------------

def test_one_trace_per_capacity_bucket(db):
    """Re-binding runtime params on a compacted plan re-executes the same
    jitted program: one scalar trace, one vmapped trace per batch bucket,
    no re-staging — the capacity buckets are static shapes of one entry."""
    build, defaults = PARAM_QUERIES["q12"]
    cache = PlanCache(db)
    cq, runtime = cache.get(build(), preset("opt"), defaults)
    assert cq.compaction_points, "q12's receipt-window plan must compact"
    before = compile_mod.STAGINGS
    alt = {k: v for k, v in ALT_BINDINGS["q12"].items() if k in runtime}
    for b in (runtime, dict(runtime, **alt), runtime):
        cache.execute(build(), preset("opt"), dict(defaults, **b))
    assert cq.n_traces == 1
    assert compile_mod.STAGINGS - before == 0
    cache.run_many(cq, [runtime, dict(runtime, **alt)])
    cache.run_many(cq, [dict(runtime, **alt), runtime])
    assert cq.n_batch_traces == 1          # one bucket-2 trace, reused
    assert cq.n_overflows == 0


def test_capacities_are_part_of_the_plan_key(db):
    cache = PlanCache(db)
    s_on, s_off = preset("opt"), _mask_only(preset("opt"))
    plan = QUERIES["q3"]()
    key_on = cache.key_for(plan, s_on)
    key_off = cache.key_for(plan, s_off)
    assert key_off[-1] == ()
    # the key's capacity vector is exactly the compiled entry's static
    # shapes, and deterministic: same plan, same buckets
    cq, _ = cache.get(QUERIES["q3"](), s_on)
    assert key_on[-1] == cq.capacities and cq.capacities
    assert cache.key_for(QUERIES["q3"](), s_on) == key_on


def test_cache_counts_compactions_and_overflows(db):
    cache = PlanCache(db)
    cache.execute(QUERIES["q3"](), preset("opt"))
    cache.execute(QUERIES["q3"](), preset("opt"))
    assert cache.stats.compactions == 2
    assert cache.stats.overflows == 0
    key, plan = None, _overflowing_plan()
    cache.execute(plan, preset("opt"))
    assert cache.stats.compactions == 3
    assert cache.stats.overflows == 1


# ---------------------------------------------------------------------------
# dense-agg group-count estimate (ROADMAP residual: q3 top-k)
# ---------------------------------------------------------------------------

def test_dense_agg_output_compacts_before_sort(db):
    """The balls-in-bins group estimate (live key population from join
    match fractions x distinct-count stats) must come in tight enough to
    plant a Compact between q3's Sort and its dense Agg — the naive
    min(valid rows, domain) bound never did — without overflowing, and
    with oracle-identical results."""
    cq = CompiledQuery(QUERIES["q3"](), db, preset("opt"))
    planted = [n for n in ir.walk(cq.plan) if isinstance(n, ir.Sort)
               and isinstance(n.child, Compact)
               and isinstance(n.child.child, Agg)
               and n.child.child.strategy == "dense"]
    assert planted, "no Compact planted between Sort and the dense Agg"
    point = planted[0].child
    domain = 1
    for d in planted[0].child.child.domains:
        domain *= d
    # the win the planner demands: capacity at least 2x under the
    # uncompacted dense output the Sort would otherwise consume
    assert point.capacity * 2 <= domain
    got = cq.run()
    assert cq.n_overflows == 0, f"overflowed {cq.capacities}"
    assert_same(got, VolcanoEngine(db).execute(QUERIES["q3"]()),
                sort_insensitive=True)


def test_dense_group_estimate_tightens_but_stays_safe(db):
    """Param-bound q3 under both default and alternative bindings: the
    tightened capacities must neither overflow nor drift from the oracle
    (the estimate only narrows capacity, never correctness)."""
    build, defaults = PARAM_QUERIES["q3"]
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)
    for bindings in (defaults, dict(defaults, **ALT_BINDINGS["q3"])):
        got = cache.execute(build(), preset("opt"), bindings)
        assert_same(got, oracle.execute(build(), bindings),
                    sort_insensitive=True)
    assert cache.stats.overflows == 0
