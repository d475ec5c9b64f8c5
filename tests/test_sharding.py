"""Sharded execution: co-partitioned TPC-H through shard_map must match
the single-device engine (and the Volcano oracle) exactly, and every
Exchange the Sharding pass plants must be load-bearing.

conftest.py forces 8 virtual CPU devices before the first jax import;
when that failed (jax was already loaded), the mesh tests skip rather
than mis-measure against a 1-device "mesh"."""
import copy
import dataclasses
import math
import re

import pytest

from repro.core import CompiledQuery, VolcanoEngine, preset
from repro.core import ir
from repro.core.mesh import resolve_shards
from repro.core.passes.pipeline import Settings, optimize
from repro.core.plan_cache import PlanCache
from repro.relational.queries import QUERIES

from test_queries import SORT_INSENSITIVE, assert_same


def _devices() -> int:
    import jax

    return len(jax.devices())


def _needs(n):
    return pytest.mark.skipif(
        _devices() < n,
        reason=f"needs {n} simulated devices (jax imported before conftest "
               "could set XLA_FLAGS)")


def sharded(n: int) -> Settings:
    return dataclasses.replace(preset("opt"), shards=n)


@pytest.fixture(scope="module")
def oracle(db):
    eng = VolcanoEngine(db)
    return {name: eng.execute(fn()) for name, fn in QUERIES.items()}


# -- tier-1 smoke: 2-device mesh ---------------------------------------------

@_needs(2)
@pytest.mark.parametrize("qname", ["q1", "q6", "q12"])
def test_two_shard_smoke(db, oracle, qname):
    """Fast 2-device check: a routed-table scan+agg (q1/q6) and one
    co-partitioned lineitem-orders join (q12) against the oracle."""
    cq = CompiledQuery(QUERIES[qname](), db, sharded(2))
    assert cq.n_shards == 2
    res = cq.run()
    assert_same(res, oracle[qname], qname in SORT_INSENSITIVE)
    # running twice exercises the per-shard observation merge path
    res2 = cq.run()
    assert_same(res2, oracle[qname], qname in SORT_INSENSITIVE)


@_needs(2)
def test_exchange_placement_minimal(db):
    """Co-partitioned pipelines shard without data movement: q6 (no join)
    and q12 (lineitem routed to the orders partition root) must lower
    with zero Exchange nodes; the verifier runs inside optimize()."""
    for qname in ("q6", "q12"):
        lowered = optimize(QUERIES[qname](), db, sharded(2))
        n_ex = sum(isinstance(n, ir.Exchange) for n in ir.walk(lowered))
        assert n_ex == 0, f"{qname}: gratuitous Exchange planted"


@_needs(2)
def test_exchange_count_bounded(db):
    """Per-query Exchange count never exceeds the number of eligible
    consumers (non-co-partitioned join builds + global sort/limit/agg
    inputs + partitioned root).  The verifier's `exchange-count` rule
    enforces the bound inside optimize(); this re-counts it end to end."""
    for qname in sorted(QUERIES):
        lowered = optimize(QUERIES[qname](), db, sharded(2))
        n_ex = sum(isinstance(n, ir.Exchange) for n in ir.walk(lowered))
        n_joins = sum(isinstance(n, ir.Join) for n in ir.walk(lowered))
        n_tail = sum(isinstance(n, (ir.Sort, ir.Limit, ir.Agg))
                     for n in ir.walk(lowered))
        assert n_ex <= n_joins + n_tail + 1, qname


def test_mesh_shape_joins_cache_key(db):
    plan = QUERIES["q6"]
    cache = PlanCache(db)
    k1 = cache.key_for(plan(), preset("opt"))
    if _devices() >= 2:
        k2 = cache.key_for(plan(), sharded(2))
        assert k1 != k2
    # auto (shards=0) must key on the RESOLVED device count, not the raw 0
    k_auto = cache.key_for(plan(), preset("opt-shard"))
    assert resolve_shards(preset("opt-shard")) in k_auto[:-1]
    assert k_auto != k1


def test_batch_compile_rejects_mesh(db):
    if _devices() < 2:
        pytest.skip("needs 2 devices")
    from repro.core.compile import CompiledQueryBatch

    with pytest.raises(NotImplementedError):
        CompiledQueryBatch([QUERIES["q6"]()], db, sharded(2))


# -- full sweep: 4-device mesh ------------------------------------------------

@_needs(4)
@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_four_shard_matches_oracle(db, oracle, qname):
    cq = CompiledQuery(QUERIES[qname](), db, sharded(4))
    assert cq.n_shards == 4
    assert_same(cq.run(), oracle[qname], qname in SORT_INSENSITIVE)


# -- the four-chip preset (`opt-shard4`: chipbench's power-opt-4chip) --------

POWER = ("q1", "q3", "q4", "q5", "q6", "q7", "q9full", "q10", "q12", "q13",
         "q14", "q17", "q18", "q19")
# a collective's result shape in the lowered HLO: `%x = s32[400]{0} all-gather(`
_HLO_COLLECTIVE = re.compile(
    r"= (.*?) (all-gather|all-reduce|reduce-scatter|all-to-all)\(")
_HLO_SHAPE = re.compile(r"\b(pred|s32|u32|f32)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s32": 4, "u32": 4, "f32": 4}


def _hlo_collective_bytes(text: str) -> int:
    """Bytes of every collective's result in an HLO module's text."""
    total = 0
    for m in _HLO_COLLECTIVE.finditer(text):
        for dtype, dims in _HLO_SHAPE.findall(m.group(1)):
            total += _BYTES[dtype] * math.prod(
                int(d) for d in dims.split(",") if d)
    return total


@_needs(4)
def test_four_chip_preset_compiles_every_entry_at_four_shards(db):
    cache = PlanCache(db)
    for q in POWER:
        cache.get(QUERIES[q](), preset("opt-shard4"))
    entries = list(cache._entries.values())
    assert len(entries) == len(POWER)
    assert {cq.n_shards for cq in entries} == {4}


@_needs(4)
def test_four_chip_preset_keeps_its_planned_capacities(db, oracle):
    # with feedback, q12's underused compaction points shrink after
    # `compact_shrink_after` runs, and the shrink stages a second program
    s = preset("opt-shard4")
    for settings, shrinks in ((dataclasses.replace(s, compact_feedback=True),
                               1), (s, 0)):
        cache = PlanCache(db)
        for _ in range(s.compact_shrink_after + 2):
            got = cache.execute(QUERIES["q12"](), settings)
        assert (cache.stats.shrinks, cache.stats.compiles) == \
            (shrinks, 1 + shrinks)
        assert_same(got, oracle["q12"], "q12" in SORT_INSENSITIVE)


@_needs(4)
def test_exchange_bytes_are_the_gathered_and_combined_shapes(db, oracle):
    import jax

    cq = CompiledQuery(QUERIES["q18"](), db, preset("opt-shard4"))
    assert any(isinstance(n, ir.Exchange) for n in ir.walk(cq.plan))
    assert_same(cq.run(), oracle["q18"], "q18" in SORT_INSENSITIVE)
    got = cq.exchange_bytes
    assert cq.n_traces == 1                  # read from the dispatch's trace
    hlo = jax.jit(cq.fn).lower(cq.bind()).as_text(dialect="hlo")
    assert got == _hlo_collective_bytes(hlo) > 0

    single = CompiledQuery(QUERIES["q18"](), db, preset("opt"))
    single.run()
    assert single.exchange_bytes == 0


def test_four_chip_preset_refuses_fewer_devices(db, monkeypatch):
    import jax

    few = jax.devices()[:3]
    monkeypatch.setattr(jax, "devices", lambda *a: few)
    with pytest.raises(ValueError, match="shards=4"):
        resolve_shards(preset("opt-shard4"))
    with pytest.raises(ValueError, match="shards=4"):
        PlanCache(db).get(QUERIES["q6"](), preset("opt-shard4"))
