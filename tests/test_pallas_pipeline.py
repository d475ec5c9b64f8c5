"""The opt-pallas rung end-to-end: single-pass compaction swap-in, the
fused filter→compact pipeline, in-kernel selective aggregation, and the
translated (CSR key→slot) pk_gather build — all against the Volcano
oracle / the plain `opt` rung, with kernel-call counters proving the
kernel paths actually executed."""
import copy

import numpy as np
import pytest

from repro.core import CompiledQuery, PlanCache, VolcanoEngine, ir, preset
from repro.core.expr import Cmp, col, lit
from repro.core.ir import Agg, AggSpec, Compact, Join, Scan, Select
from repro.relational.queries import QUERIES
from test_queries import SORT_INSENSITIVE, assert_same


@pytest.fixture
def kernel_calls(monkeypatch):
    """Count invocations of each kernel entry point (the operator layer
    calls through `repro.kernels.ops`, so wrapping there sees them all)."""
    import repro.kernels.ops as kops

    calls = {"compact": 0, "compact_pred": 0, "selective_agg": 0,
             "filter_agg": 0}

    def wrap(name, fn):
        def g(*a, **k):
            calls[name] += 1
            return fn(*a, **k)
        return g

    monkeypatch.setattr(kops, "compact", wrap("compact", kops.compact))
    monkeypatch.setattr(kops, "compact_pred",
                        wrap("compact_pred", kops.compact_pred))
    monkeypatch.setattr(kops, "selective_agg_query",
                        wrap("selective_agg", kops.selective_agg_query))
    monkeypatch.setattr(kops, "filter_agg_query",
                        wrap("filter_agg", kops.filter_agg_query))
    return calls


# which kernel entry point each representative query must exercise:
#   q3  — plain single-pass compact (mask from a join survives upstream)
#   q6  — the whole selective pipeline (pred + scalar agg, no compact)
#   q12 — fused pred + compact (Select absorbed into the compaction kernel)
#   q17 — fused pred + TRANSLATED compact on a pk_gather build side
_EXPECT = {"q3": "compact", "q6": "selective_agg", "q12": "compact_pred",
           "q17": "compact_pred"}


@pytest.mark.parametrize("qname", sorted(_EXPECT))
def test_pallas_rung_matches_oracle(db, qname, kernel_calls):
    plan = QUERIES[qname]()
    want = VolcanoEngine(db).execute(copy.deepcopy(plan))
    cq = CompiledQuery(copy.deepcopy(plan), db, preset("opt-pallas"))
    got = cq.run()
    assert_same(got, want, qname in SORT_INSENSITIVE)
    assert kernel_calls[_EXPECT[qname]] > 0, \
        f"{qname} never hit the {_EXPECT[qname]} kernel path"
    assert cq.n_overflows == 0


def test_q17_plants_translated_build_compact(db):
    """The Compaction pass compacts q17's selective pk_gather build under
    use_pallas (translate point), which the positional-alignment verifier
    must accept — and must keep refusing without the translation."""
    cq = CompiledQuery(QUERIES["q17"](), db, preset("opt-pallas"))
    tr = [n for n in ir.walk(cq.plan)
          if isinstance(n, ir.Compact) and n.translate and n.capacity > 0]
    assert tr, "no translate point planted on q17's build side"
    # without the kernel path the same site must NOT be planted: pk_gather
    # stays positional and the build frame stays intact
    cq_opt = CompiledQuery(QUERIES["q17"](), db, preset("opt"))
    assert not any(n.translate for n in ir.walk(cq_opt.plan)
                   if isinstance(n, ir.Compact))


def _translated_build_plan(cap: int) -> ir.Plan:
    """A hand-lowered pk_gather whose build side is a hand-planted
    translate-Compact: stream lineitem, build the sub-64-row slice of
    part, carry one build column through the join into a scalar agg."""
    build = Compact(
        Select(Scan("part"), Cmp("<", col("p_size"), lit(10.0))),
        cap, translate=True)
    j = Join(Scan("lineitem"), build, "l_partkey", "p_partkey",
             strategy="pk_gather", build_table="part")
    return Agg(j, [], [AggSpec("s", "sum", col("p_size")),
                       AggSpec("c", "count")])


def _uncompacted_twin(plan: ir.Plan) -> ir.Plan:
    from repro.core.passes.compaction import strip_compaction

    return strip_compaction(copy.deepcopy(plan))


@pytest.mark.parametrize("pname", ["opt", "opt-pallas"])
def test_translated_pk_gather_matches_uncompacted(db, pname):
    """The CSR slot_of probe (Pallas kernel under opt-pallas, the XLA
    cumsum fallback under opt) gives bit-identical results to the
    positional join over the uncompacted build."""
    # part@sf0.01 has 2000 rows, ~360 pass the filter: 1024 really
    # compacts (cap < nrows) without overflowing (cap > valid rows)
    plan = _translated_build_plan(1024)
    want = CompiledQuery(_uncompacted_twin(plan), db, preset("opt")).run()
    cq = CompiledQuery(plan, db, preset(pname))
    got = cq.run()
    assert cq.n_overflows == 0
    tr = [n for n in ir.walk(cq.plan)
          if isinstance(n, ir.Compact) and n.translate and n.capacity > 0]
    assert tr, "hand-planted translate point was optimized away"
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-3,
                                   err_msg=k)


def test_translated_build_overflow_falls_back(db):
    """An undershot translate capacity drops probe targets (slots past the
    bucket) — the overflow flag must fire and the uncompacted twin must
    deliver the correct result anyway."""
    plan = _translated_build_plan(64)     # far below the valid build rows
    want = CompiledQuery(_uncompacted_twin(plan), db, preset("opt")).run()
    cq = CompiledQuery(plan, db, preset("opt-pallas"))
    got = cq.run()
    assert cq.n_overflows == 1
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-3,
                                   err_msg=k)


def test_fused_interception_bails_on_unsafe_predicate(db):
    """A Compact over a Select whose predicate needs 2-D string blocks
    (not kernel-representable) must fall back to ordinary evaluation —
    same results, no crash."""
    from repro.core.expr import StrContainsWord

    plan = Agg(
        Compact(Select(Scan("part"), StrContainsWord("p_name", "green")),
                1024),
        [], [AggSpec("c", "count")])
    want = CompiledQuery(copy.deepcopy(plan), db, preset("opt")).run()
    got = CompiledQuery(copy.deepcopy(plan), db, preset("opt-pallas")).run()
    np.testing.assert_array_equal(got["c"], want["c"])


def test_pallas_rung_run_many(db):
    """Batched (vmapped) execution through the kernel paths: per-slot
    results equal scalar runs."""
    from repro.relational.queries import PARAM_QUERIES

    build, defaults = PARAM_QUERIES["q6"]
    cache = PlanCache(db)
    cq, runtime = cache.get(build(), preset("opt-pallas"), defaults)
    b2 = dict(runtime, qty_max=float(runtime["qty_max"]) + 1.0)
    results = cq.run_many([runtime, b2])
    for got, b in zip(results, [runtime, b2]):
        want = cq.run(b)
        for k in got:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6,
                                       err_msg=k)
