"""Adaptive capacity feedback: observed per-point counts drive re-planning
(overflow -> re-plan with measured headroom, sustained underuse -> shrink),
sketch-based initial estimates let parameterized plans compact, and batch
padding is masked out of overflow accounting."""
import dataclasses

import numpy as np
import pytest

from repro.core import CompiledQuery, PlanCache, VolcanoEngine, preset
from repro.core import compile as compile_mod
from repro.core.expr import Cmp, col, lit
from repro.core.ir import Agg, AggSpec, Compact, Scan, Select
from repro.core.passes.compaction import observed_bucket
from repro.relational.queries import PARAM_QUERIES
from repro.relational.schema import days
from test_queries import assert_same

# q3_param bindings: SELECTIVE leaves few lineitem rows past the shipdate
# cutoff (small planted capacities), WIDE leaves many (guaranteed overflow
# of capacities planned for SELECTIVE).
SELECTIVE = {"cutoff": days("1998-06-01"), "segment": "BUILDING", "topn": 10}
WIDE = {"cutoff": days("1995-03-15"), "segment": "BUILDING", "topn": 10}


def _settings(replan_after=2, shrink_after=3):
    return dataclasses.replace(preset("opt"),
                               compact_replan_after=replan_after,
                               compact_shrink_after=shrink_after)


# ---------------------------------------------------------------------------
# sketch-based initial estimates
# ---------------------------------------------------------------------------

def test_quantile_sketch_cdf(db):
    t = db.table("lineitem")
    q = t.quantile_sketch("l_quantity")
    assert np.all(np.diff(q) >= 0)
    arr = t.col("l_quantity")
    for v in (1.0, 24.0, 50.0):
        true = float(np.count_nonzero(arr <= v)) / arr.size
        assert abs(t.cdf("l_quantity", v) - true) < 0.02
    assert t.cdf("l_quantity", -1e9) == 0.0
    assert t.cdf("l_quantity", 1e9) == 1.0


def test_pair_sketch_measures_col_vs_col(db):
    t = db.table("lineitem")
    frac = t.pair_frac("l_commitdate", "<", "l_receiptdate")
    x, y = t.col("l_commitdate"), t.col("l_receiptdate")
    assert frac == float(np.count_nonzero(x < y)) / t.nrows
    assert 0.0 < frac < 1.0
    # cached: second call returns the same object path
    assert t.pair_frac("l_commitdate", "<", "l_receiptdate") == frac


@pytest.mark.parametrize("qname", ["q3", "q12"])
def test_param_plans_now_compact(db, qname):
    """The whole point of the initial estimates: Param-bounded predicates
    used to be estimated at selectivity 1.0, so parameterized plans never
    compacted.  With the quantile/pair sketches fed by the first-seen
    bindings, the q3/q12 classes plant points immediately."""
    build, defaults = PARAM_QUERIES[qname]
    cache = PlanCache(db)
    cq, _ = cache.get(build(), preset("opt"), defaults)
    assert cq.compaction_points > 0, f"{qname}_param planted no points"
    n_li = db.table("lineitem").nrows
    for cap in cq.capacities:
        assert cap & (cap - 1) == 0 and cap < n_li
    # and the planted capacities hold the default binding: no overflow
    cache.execute(build(), preset("opt"), defaults)
    assert cq.n_overflows == 0


# ---------------------------------------------------------------------------
# the feedback loop: overflow -> re-plan, underuse -> shrink
# ---------------------------------------------------------------------------

def test_overflow_feedback_replans_to_measured_capacity(db):
    """Forced-undershoot estimate (plan compiled for a selective binding)
    -> k overflows under a wide binding -> re-plan from observed counts ->
    subsequent wide bindings run compacted with zero overflows."""
    build, _ = PARAM_QUERIES["q3"]
    s = _settings(replan_after=2)
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)

    first = cache.execute(build(), s, SELECTIVE)
    assert_same(first, oracle.execute(build(), SELECTIVE),
                sort_insensitive=True)
    cq0, _ = cache.get(build(), s, SELECTIVE)
    caps0 = cq0.capacities
    assert cq0.compaction_points > 0

    # k wide bindings: every one overflows the selective-planned buckets
    # (results stay correct via the uncompacted twin)
    for _ in range(2):
        got = cache.execute(build(), s, WIDE)
        assert_same(got, oracle.execute(build(), WIDE),
                    sort_insensitive=True)
    assert cq0.n_overflows == 2
    assert cache.stats.replans == 1
    assert cache.stats.shrinks == 0

    # the re-planned entry: fresh compile, measured capacities, and the
    # wide binding now runs compacted with zero overflows
    before = compile_mod.STAGINGS
    got = cache.execute(build(), s, WIDE)
    assert_same(got, oracle.execute(build(), WIDE), sort_insensitive=True)
    cq1, _ = cache.get(build(), s, WIDE)
    assert cq1 is not cq0
    assert cq1.n_overflows == 0
    assert cq1.capacities != caps0
    # capacities come from the observed max counts: each re-planned point
    # is the pow2 bucket just above what was measured
    for pid, cap in cq1.point_caps.items():
        if pid in cq0.observed_max:
            assert cap == observed_bucket(cq0.observed_max[pid])
    # one retrace per direction: the transition compiled exactly once
    # (compile + its overflow-twin are both counted by STAGINGS)
    assert compile_mod.STAGINGS - before <= 2
    cache.execute(build(), s, WIDE)
    assert cq1.n_overflows == 0 and cache.stats.replans == 1


def test_underuse_feedback_shrinks_capacity(db):
    """Oversized capacity (plan compiled for a wide binding) -> k
    consecutive large underuses under a selective binding -> shrink to the
    measured bucket; results checked against the oracle throughout."""
    build, _ = PARAM_QUERIES["q3"]
    s = _settings(shrink_after=3)
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)

    cache.execute(build(), s, WIDE)
    cq0, _ = cache.get(build(), s, WIDE)
    caps0 = cq0.capacities
    assert cq0.compaction_points > 0

    for _ in range(3):
        got = cache.execute(build(), s, SELECTIVE)
        assert_same(got, oracle.execute(build(), SELECTIVE),
                    sort_insensitive=True)
    assert cache.stats.shrinks == 1
    assert cache.stats.replans == 0

    got = cache.execute(build(), s, SELECTIVE)
    assert_same(got, oracle.execute(build(), SELECTIVE),
                sort_insensitive=True)
    cq1, _ = cache.get(build(), s, SELECTIVE)
    assert cq1 is not cq0
    assert sum(cq1.capacities) < sum(caps0)
    assert cq1.n_overflows == 0


def test_feedback_loop_batched(db):
    """The same convergence through execute_many: wide batches overflow
    per-slot, trigger the re-plan, and the converged entry serves batches
    compacted with zero overflows."""
    build, _ = PARAM_QUERIES["q3"]
    s = _settings(replan_after=2)
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)

    cache.execute(build(), s, SELECTIVE)
    cq0, _ = cache.get(build(), s, SELECTIVE)

    wides = [dict(WIDE), dict(WIDE, cutoff=days("1995-04-15"))]
    got = cache.execute_many(build(), s, wides)
    for g, b in zip(got, wides):
        assert_same(g, oracle.execute(build(), b), sort_insensitive=True)
    assert cq0.n_overflows == 2
    assert cache.stats.replans == 1

    got = cache.execute_many(build(), s, wides)
    for g, b in zip(got, wides):
        assert_same(g, oracle.execute(build(), b), sort_insensitive=True)
    cq1, _ = cache.get(build(), s, WIDE)
    assert cq1 is not cq0 and cq1.n_overflows == 0


def test_shrink_decay_survives_a_later_replan(db):
    """A shrink decays the recorded maxima to the streak window; a later
    modest overflow must re-plan to the *measured* demand, not resurrect
    the pre-shrink spike-era capacities (docs §6: a historical spike
    cannot pin capacity up)."""
    build, _ = PARAM_QUERIES["q3"]
    tiny = dict(WIDE, cutoff=days("1998-11-01"))    # deep underuse
    medium = dict(WIDE, cutoff=days("1998-06-01"))  # modest overflow
    s = _settings(replan_after=1, shrink_after=2)
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)

    cache.execute(build(), s, WIDE)
    cq_wide, _ = cache.get(build(), s, WIDE)
    wide_caps = dict(cq_wide.point_caps)

    for _ in range(3):
        cache.execute(build(), s, tiny)
    assert cache.stats.shrinks >= 1

    # modest overflow of the shrunk buckets -> re-plan
    got = cache.execute(build(), s, medium)
    assert_same(got, oracle.execute(build(), medium), sort_insensitive=True)
    assert cache.stats.replans == 1
    got = cache.execute(build(), s, medium)
    assert_same(got, oracle.execute(build(), medium), sort_insensitive=True)
    cq_new, _ = cache.get(build(), s, medium)
    assert cq_new.n_overflows == 0
    # re-planned shared points sit at measured headroom, strictly below
    # the estimate-era wide capacities — the spike did not come back
    shared = set(cq_new.point_caps) & set(wide_caps)
    assert shared
    for pid in shared:
        assert cq_new.point_caps[pid] < wide_caps[pid]


def test_feedback_off_never_replans(db):
    build, _ = PARAM_QUERIES["q3"]
    s = dataclasses.replace(_settings(replan_after=1),
                            compact_feedback=False)
    cache = PlanCache(db)
    cache.execute(build(), s, SELECTIVE)
    cq0, _ = cache.get(build(), s, SELECTIVE)
    for _ in range(3):
        cache.execute(build(), s, WIDE)
    assert cq0.n_overflows == 3
    assert cache.stats.replans == 0 and cache.stats.shrinks == 0
    cq1, _ = cache.get(build(), s, WIDE)
    assert cq1 is cq0


# initial selective binding overlay per template, applied over the
# template's defaults: the initial binding undershoots the steady (default)
# one, so the feedback loop has something to correct.
SCHEDULES = {
    "q3": {"cutoff": days("1998-11-01")},
    "q6": {"qty_max": 2.0},
    "q12": {"receipt_lo": days("1994-01-01"),
            "receipt_hi": days("1994-02-01")},
    "q14": {"ship_lo": days("1995-09-01"),
            "ship_hi": days("1995-09-08")},
}
STEADY_RUNS = 8


@pytest.mark.parametrize("qname", sorted(SCHEDULES))
def test_binding_shift_converges_and_matches_oracle(db, qname):
    """A plan compiled for a selective binding, then driven by the steady
    binding under the default feedback settings: every answer equals the
    oracle's, and by the last steady run the entry no longer overflows
    (q6/q14 end in fusing scalar aggregations and plant no points)."""
    build, defaults = PARAM_QUERIES[qname]
    initial = dict(defaults, **SCHEDULES[qname])
    s = preset("opt")
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)

    assert_same(cache.execute(build(), s, initial),
                oracle.execute(build(), initial), sort_insensitive=True)
    want = oracle.execute(build(), defaults)
    for _ in range(STEADY_RUNS):
        overflows = cache.stats.overflows
        assert_same(cache.execute(build(), s, defaults), want,
                    sort_insensitive=True)
    assert cache.stats.overflows == overflows, \
        f"{qname}: still overflowing after {STEADY_RUNS} steady bindings"


# ---------------------------------------------------------------------------
# batch padding is masked out of overflow accounting (satellite bugfix)
# ---------------------------------------------------------------------------

def test_padding_slots_do_not_count_as_overflows(db):
    """3 bindings pad to a 4-bucket by repeating the last one; with the
    last binding overflowing a hand-planted 64-row point, exactly the
    real slots (here: one) may count — the pad slot echoes the overflow
    but nobody asked for its rows, so it must trigger neither accounting
    nor a fallback re-run."""
    build, defaults = PARAM_QUERIES["q6"]
    plan = build()
    assert isinstance(plan.child, Select)
    plan = Agg(Compact(plan.child, 64), [], plan.aggs)
    cq = CompiledQuery(plan, db, preset("opt"), params=defaults)
    tiny = dict(defaults, qty_max=1.0)      # l_quantity < 1: zero rows
    bindings = [tiny, tiny, defaults]       # only the LAST slot overflows
    results = cq.run_many(bindings)
    assert cq.n_overflows == 1, \
        "pad slot (a repeat of the overflowing last binding) was counted"
    for got, b in zip(results, bindings):
        want = cq.run(b)
        for k in got:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_observed_counts_are_true_counts(db):
    """The staged count is the cumsum total over the full mask — exact
    even when it exceeds capacity (that magnitude is what re-planning
    uses), not clipped at the bucket."""
    sel = Select(Scan("lineitem"), Cmp("<", col("l_quantity"), lit(26.0)))
    plan = Agg(Compact(sel, 64), [],
               [AggSpec("c", "count")])
    cq = CompiledQuery(plan, db, preset("opt"))
    res = cq.run()
    true_rows = int(res["c"][0])
    assert true_rows > 64
    assert cq.observed_max == {"h0": true_rows}


def test_hand_planted_point_replans_from_observed(db):
    """PR-5 residual: hand-planted Compact nodes got their counts observed
    but the pass's pre-existing-point branch never consulted the feedback
    store, so an undershot hand capacity overflowed on every execution
    forever.  The pass now assigns hand points stable h-ids and applies
    the observed override exactly like planted points."""
    def build():
        sel = Select(Scan("lineitem"), Cmp("<", col("l_quantity"), lit(2.0)))
        return Agg(Compact(sel, 64), [], [AggSpec("c", "count")])

    s = _settings(replan_after=1)
    cache = PlanCache(db)
    res = cache.execute(build(), s)
    true_rows = int(res["c"][0])
    assert true_rows > 64                    # the hand capacity undershot
    assert cache.stats.replans == 1          # ... and the overflow re-planned

    cq, _ = cache.get(build(), s)
    assert cq.point_caps["h0"] == observed_bucket(true_rows)
    res2 = cache.execute(build(), s)
    assert int(res2["c"][0]) == true_rows
    assert cq.n_overflows == 0 and cache.stats.replans == 1
