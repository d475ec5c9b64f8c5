"""Rank routes: the compaction and probe primitives behind `op.compact` and
the generic join (`core/backend.py`).

Every compaction route must give exactly what the binary search gives:
idx (valid row ids in order, pad slots n-1, the first `capacity` rows on
overflow) and count; the probe must give `np.searchsorted(side="left")`.
The rule must pick each route on its side of its crossover, the sharded
plans must answer as the Volcano oracle whichever route is forced, and
`CompiledQuery.rank_routes` must count the sites a program staged."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CompiledQuery, VolcanoEngine, preset
from repro.core import backend as be
from repro.core.compile import _rank_sites
from repro.relational.queries import QUERIES

from test_queries import SORT_INSENSITIVE, assert_same

I32MAX = np.int32(2**31 - 1)
U32MAX = np.uint32(2**32 - 1)


def _np_compact(mask: np.ndarray, cap: int):
    valid = np.flatnonzero(mask)
    idx = np.full(cap, len(mask) - 1, dtype=np.int32)
    k = min(cap, valid.size)
    idx[:k] = valid[:k]
    return idx, valid.size


def _mask(kind: str, n: int = 4096) -> np.ndarray:
    rng = np.random.default_rng(7)
    if kind == "none":
        return np.zeros(n, bool)
    if kind == "all":
        return np.ones(n, bool)
    return rng.random(n) < {"1%": 0.01, "50%": 0.5}[kind]


def _capacities(mask: np.ndarray) -> list:
    count = int(mask.sum())
    # 1, exactly the count, past it, and (where any rows survive) below
    # the count: the overflow that keeps the first `cap` rows
    caps = {1, max(count, 1), count + 17}
    if count > 2:
        caps.add(count // 2)
    return sorted(caps)


_CASES = [(kind, cap) for kind in ("none", "all", "1%", "50%")
          for cap in _capacities(_mask(kind))]


@pytest.mark.parametrize("route", sorted(be._COMPACT))
@pytest.mark.parametrize("kind,cap", _CASES)
def test_compact_route_equals_search_and_numpy(route, kind, cap):
    mask = _mask(kind)
    idx, count = jax.jit(be._COMPACT[route], static_argnums=1)(
        jnp.asarray(mask), cap)
    s_idx, s_count = jax.jit(be._compact_search, static_argnums=1)(
        jnp.asarray(mask), cap)
    want_idx, want_count = _np_compact(mask, cap)
    assert idx.dtype == jnp.int32 and idx.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(s_idx))
    assert int(count) == int(s_count) == want_count


@pytest.mark.parametrize("route", sorted(be._COMPACT))
def test_compact_route_under_vmap(route):
    masks = np.stack([_mask(k, 1000) for k in ("none", "all", "1%", "50%")])
    cap = 64
    idx, count = jax.jit(jax.vmap(lambda m: be._COMPACT[route](m, cap)))(
        jnp.asarray(masks))
    for row, m in enumerate(masks):
        want_idx, want_count = _np_compact(m, cap)
        np.testing.assert_array_equal(np.asarray(idx[row]), want_idx)
        assert int(count[row]) == want_count


def _probe_case(kind: str):
    rng = np.random.default_rng(11)
    if kind == "i32-sentinel":
        # a masked build: its rows set to I32MAX sort to the tail
        keys = rng.choice(10_000, 500, replace=False).astype(np.int32)
        keys[rng.random(500) < 0.3] = I32MAX
        v = rng.integers(0, 10_000, 2000).astype(np.int32)
        v[:3] = (0, 9_999, I32MAX - 1)
        return np.sort(keys), v
    if kind == "u32-sentinel":
        # the composite-key pack: uint32 keys past 2**31, masked to 2**32-1
        keys = rng.choice(2**32 - 2, 500, replace=False).astype(np.uint32)
        keys[rng.random(500) < 0.3] = U32MAX
        v = np.concatenate([keys[:700 // 2], rng.integers(
            0, 2**32 - 2, 1000, dtype=np.uint32)]).astype(np.uint32)
        return np.sort(keys), v
    if kind == "absent":
        # stream keys between, below and above every build key
        keys = np.arange(0, 2000, 2, dtype=np.int32)
        return keys, np.arange(-5, 2010, dtype=np.int32)
    if kind == "empty-stream":
        return np.arange(100, dtype=np.int32), np.zeros(0, np.int32)
    raise KeyError(kind)


@pytest.mark.parametrize("kind", ["i32-sentinel", "u32-sentinel", "absent",
                                  "empty-stream"])
def test_probe_equals_numpy(kind):
    a, v = _probe_case(kind)
    got = jax.jit(be.JaxBackend().searchsorted)(jnp.asarray(a),
                                                jnp.asarray(v))
    want = np.searchsorted(a, v, side="left")
    assert got.shape == v.shape
    np.testing.assert_array_equal(np.asarray(got), want)


def test_probe_under_vmap():
    a, v = _probe_case("absent")
    vs = np.stack([v, v[::-1], v + 3])
    jb = be.JaxBackend()
    got = jax.jit(jax.vmap(lambda x: jb.searchsorted(jnp.asarray(a), x)))(
        jnp.asarray(vs))
    for row in range(len(vs)):
        np.testing.assert_array_equal(
            np.asarray(got[row]), np.searchsorted(a, vs[row], side="left"))


# -- the rules -------------------------------------------------------------

@pytest.mark.parametrize("n", [1_500_000, 6_000_000, 24_000_000])
def test_compact_rule_crossover(n):
    """Below the crossover the search's gathers are the cheaper work; one
    slot past it the scatter over every row is."""
    edge = int(be._SCATTER_AT * n / be._levels(n))  # largest for search
    assert be.compact_route(n, edge) == "search"
    assert be.compact_route(n, edge + 1) == "scatter"
    assert be.compact_route(n, 1) == "search"


def test_backend_counts_and_scopes_each_site(monkeypatch):
    """Each site stages under `op.rank/rank.<route>`, and the jaxpr count
    `CompiledQuery.rank_routes` reads finds one site each, under vmap
    too."""
    monkeypatch.setattr(be, "compact_route", lambda n, cap: "scatter")
    jb = be.JaxBackend()

    def f(mask, a, v):
        return jb.compact(mask, 8), jb.searchsorted(a, v), \
            jb.searchsorted(a, v)

    args = (jnp.ones(64, bool), jnp.arange(16), jnp.arange(4))
    hlo = jax.jit(f).lower(*args).as_text(dialect="hlo", debug_info=True)
    assert "op.rank/rank.scatter/" in hlo and "op.rank/rank.search/" in hlo
    for g in (f, jax.vmap(f)):
        a = args if g is f else tuple(jnp.stack([x, x]) for x in args)
        jaxpr = jax.jit(g).trace(*a).jaxpr.jaxpr
        assert _rank_sites(jaxpr) == {"scatter": 1, "search": 2}


# -- whole plans: sharded q3 and q18 with each route forced -----------------

def _devices() -> int:
    return len(jax.devices())


@pytest.fixture(scope="module")
def oracle(db):
    eng = VolcanoEngine(db)
    return {q: eng.execute(QUERIES[q]()) for q in ("q3", "q7", "q18")}


@pytest.mark.skipif(_devices() < 4, reason="needs 4 simulated devices")
@pytest.mark.parametrize("route", sorted(be._COMPACT))
@pytest.mark.parametrize("qname,sites", [("q3", {"compact": 1}),
                                         ("q18", {"search": 1})])
def test_sharded_plans_equal_oracle_with_each_route(db, oracle, monkeypatch,
                                                    route, qname, sites):
    """`opt-shard4` with `dense_agg_cap` below the order keys, so q3 and
    q18 group through the sort-based grouping as at SF 4: q3 compacts its
    gathered frame, q18 probes the gathered groups."""
    monkeypatch.setattr(be, "compact_route", lambda n, cap: route)
    n_orders = db.tables["orders"].nrows
    settings = dataclasses.replace(preset("opt-shard4"),
                                   dense_agg_cap=n_orders // 2)
    cq = CompiledQuery(QUERIES[qname](), db, settings)
    assert_same(cq.run(), oracle[qname], qname in SORT_INSENSITIVE)
    want = {(route if k == "compact" else k): v for k, v in sites.items()}
    assert cq.rank_routes == want
    assert cq.n_traces == 1                  # read from the dispatch's trace


def test_rank_routes_counts_the_staged_sites(db, oracle, monkeypatch):
    """q7 under `opt`: two probes of the 25-row nation build and two
    compaction points, each counted under the route it staged."""
    cq = CompiledQuery(QUERIES["q7"](), db, preset("opt"))
    assert_same(cq.run(), oracle["q7"], "q7" in SORT_INSENSITIVE)
    routes = cq.rank_routes
    assert sum(routes.values()) == 4 and routes["search"] >= 2
    monkeypatch.setattr(be, "compact_route", lambda n, cap: "scatter")
    forced = CompiledQuery(QUERIES["q7"](), db, preset("opt"))
    assert_same(forced.run(), oracle["q7"], "q7" in SORT_INSENSITIVE)
    assert forced.rank_routes == {"scatter": 2, "search": 2}
    assert CompiledQuery(QUERIES["q1"](), db, preset("opt")).rank_routes \
        == {}
