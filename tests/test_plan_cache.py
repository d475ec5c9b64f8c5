"""Runtime layer: plan cache hit/miss accounting, param-bound vs
literal-baked equivalence, single-staging re-binding (the compile-counter
acceptance criterion), and the concurrent query server incl. two requests
sharing one in-flight compilation."""
import threading

import numpy as np
import pytest

from repro.core import CompiledQuery, PlanCache, VolcanoEngine, preset
from repro.core import compile as compile_mod
from repro.relational.queries import (PARAM_ALT_BINDINGS as ALT_BINDINGS,
                                      PARAM_QUERIES, QUERIES)
from repro.relational.schema import days
from repro.serve.query_server import QueryServer
from test_queries import assert_same

CONFIGS = ["naive", "template", "tpch", "strdict", "opt"]


def assert_matches(got, want):
    # param results compare row-order-insensitively: ties under alternative
    # bindings may sort differently between engines
    assert_same(got, want, sort_insensitive=True)


# ---------------------------------------------------------------------------
# acceptance criterion: same parameterized query, re-bound default ->
# alternative -> default, ONE staging, every answer matching the Volcano
# oracle: q6 under every preset in CONFIGS, every other template at `opt`.
# ---------------------------------------------------------------------------

REBIND_CASES = ([(config, "q6") for config in CONFIGS]
                + [("opt", q) for q in sorted(PARAM_QUERIES) if q != "q6"])
# templates whose `opt` plan plants compaction points at this scale: each
# of their executions counts in `CacheStats.compactions`
COMPACTING = {"q3", "q12"}


@pytest.mark.parametrize("config,qname", REBIND_CASES)
def test_rebind_single_staging_matches_oracle(db, config, qname):
    build, defaults = PARAM_QUERIES[qname]
    alt = dict(defaults, **ALT_BINDINGS[qname])
    cache = PlanCache(db)
    oracle = VolcanoEngine(db)
    before = compile_mod.STAGINGS
    for bindings in (defaults, alt, defaults):
        got = cache.execute(build(), preset(config), bindings)
        want = oracle.execute(build(), bindings)
        assert_matches(got, want)
    assert compile_mod.STAGINGS - before == 1, \
        "re-binding must not re-stage/re-JIT"
    assert cache.stats == type(cache.stats)(
        hits=2, misses=1, compiles=1,
        compactions=3 if qname in COMPACTING else 0)
    # and the jitted program itself traced exactly once
    (cq,) = [cache.get(build(), preset(config), defaults)[0]]
    assert cq.n_traces == 1


@pytest.mark.parametrize("qname", sorted(PARAM_QUERIES))
def test_param_bound_equals_literal_baked(db, qname):
    """Default bindings reproduce the literal query exactly; alternative
    bindings match the oracle evaluated under the same bindings."""
    build, defaults = PARAM_QUERIES[qname]
    cache = PlanCache(db)
    got = cache.execute(build(), preset("opt"), defaults)
    literal = CompiledQuery(QUERIES[qname](), db, preset("opt")).run()
    assert_matches(got, literal)
    alt = dict(defaults, **ALT_BINDINGS[qname])
    assert_matches(cache.execute(build(), preset("opt"), alt),
                   VolcanoEngine(db).execute(build(), alt))


def test_specialize_mode_bakes_every_binding(db):
    build, defaults = PARAM_QUERIES["q6"]
    alt = dict(defaults, **ALT_BINDINGS["q6"])
    cache = PlanCache(db)
    a = cache.execute(build(), preset("opt"), defaults, mode="specialize")
    b = cache.execute(build(), preset("opt"), alt, mode="specialize")
    a2 = cache.execute(build(), preset("opt"), defaults, mode="specialize")
    assert cache.stats.compiles == 2     # one per distinct binding
    assert cache.stats.hits == 1         # repeat binding hits
    assert_matches(a, a2)
    assert not np.allclose(a["revenue"], b["revenue"])


def test_structural_params_key_the_cache(db):
    """String / limit params are compile-time: a new value is a new cache
    entry, a repeated value is a hit."""
    build, defaults = PARAM_QUERIES["q3"]
    cache = PlanCache(db)
    cache.execute(build(), preset("opt"), defaults)
    cache.execute(build(), preset("opt"), dict(defaults, cutoff=days("1995-06-15")))
    assert cache.stats.compiles == 1     # numeric param: same entry
    cache.execute(build(), preset("opt"), dict(defaults, segment="MACHINERY"))
    assert cache.stats.compiles == 2     # string param: new entry
    cache.execute(build(), preset("opt"), dict(defaults, topn=5))
    assert cache.stats.compiles == 3     # limit param: new entry
    got = cache.execute(build(), preset("opt"), dict(defaults, topn=5))
    assert cache.stats.compiles == 3
    assert len(next(iter(got.values()))) == 5


def test_missing_compile_time_binding_raises(db):
    build, defaults = PARAM_QUERIES["q3"]
    cache = PlanCache(db)
    partial = {k: v for k, v in defaults.items() if k != "segment"}
    with pytest.raises(KeyError, match="segment"):
        cache.execute(build(), preset("opt"), partial)


def test_cache_eviction_accounting(db):
    build, defaults = PARAM_QUERIES["q6"]
    cache = PlanCache(db, max_entries=1)
    cache.execute(build(), preset("opt"), defaults)
    cache.execute(build(), preset("naive"), defaults)   # distinct settings
    assert cache.stats.evictions == 1
    assert len(cache) == 1


def test_cache_lru_eviction_order_and_recompile(db):
    """max_entries overflow evicts the *least recently used* entry (a
    fresh hit protects an old entry), stats stay consistent, and
    re-inserting the evicted key recompiles exactly once."""
    build, defaults = PARAM_QUERIES["q6"]
    cache = PlanCache(db, max_entries=2)
    s_opt, s_tpch, s_naive = preset("opt"), preset("tpch"), preset("naive")
    cache.execute(build(), s_opt, defaults)
    cache.execute(build(), s_tpch, defaults)
    cache.execute(build(), s_opt, defaults)      # hit: opt becomes MRU
    cache.execute(build(), s_naive, defaults)    # evicts LRU = tpch
    assert cache.stats.evictions == 1 and len(cache) == 2
    assert cache.contains(cache.key_for(build(), s_opt, defaults))
    assert cache.contains(cache.key_for(build(), s_naive, defaults))
    assert not cache.contains(cache.key_for(build(), s_tpch, defaults))
    # stats stay consistent: every execute was one hit or one miss
    assert cache.stats.hits + cache.stats.misses == 4
    assert cache.stats.compiles == cache.stats.misses == 3
    # re-insert recompiles exactly once, then hits again
    before = compile_mod.STAGINGS
    cache.execute(build(), s_tpch, defaults)
    cache.execute(build(), s_tpch, defaults)
    assert cache.stats.compiles == 4
    assert compile_mod.STAGINGS - before == 1


def test_db_identity_uses_fingerprint_not_id(db):
    """Regression: keying on id(db) can alias a *new* database onto a
    dead one's cache entries once the allocator reuses the address.  The
    monotonic fingerprint never repeats within a process."""
    import gc

    from repro.relational import Database
    from repro.relational.queries import QUERIES

    d1 = Database({})
    f1 = d1.fingerprint
    k1 = PlanCache(d1).key_for(QUERIES["q6"](), preset("opt"))
    del d1
    gc.collect()
    seen = set()
    for _ in range(20):
        d = Database({})      # may well land on d1's freed address
        assert d.fingerprint != f1
        seen.add(d.fingerprint)
        assert PlanCache(d).key_for(QUERIES["q6"](), preset("opt")) != k1
        del d
        gc.collect()
    assert len(seen) == 20, "fingerprints must be unique across databases"
    key = PlanCache(db).key_for(QUERIES["q6"](), preset("opt"))
    assert key[2] == db.fingerprint


def test_reload_invalidates_capacity_memo_and_entries():
    """Regression: the capacity-signature memo is keyed by (plan shape,
    settings, db.fingerprint).  A `Database.reload` changes `Table.stats`
    under the same object — the fingerprint bump must invalidate both the
    memoized capacity vectors and the compiled entries, or a re-planted
    capacity computed against dead statistics gets served to new data."""
    from repro.relational import Database

    db = Database.tpch(sf=0.01, seed=0)
    cache = PlanCache(db)
    plan = QUERIES["q3"]
    k1 = cache.key_for(plan(), preset("opt"))
    caps1 = k1[-1]
    assert caps1, "q3 must plant compaction points"
    cache.execute(plan(), preset("opt"))
    assert cache.stats.compiles == 1

    small = Database.tpch(sf=0.002, seed=1)
    old_fp = db.fingerprint
    db.reload(small.tables)
    assert db.fingerprint != old_fp
    k2 = cache.key_for(plan(), preset("opt"))
    assert k2 != k1
    # the capacity vector was recomputed from the NEW table stats, not
    # reused from the stale memo (an 5x-smaller lineitem cannot plan the
    # same buckets — at worst the points vanish below compact_min_rows)
    assert k2[-1] != caps1
    # and the stale compiled entry is unreachable: fresh compile
    cache.execute(plan(), preset("opt"))
    assert cache.stats.compiles == 2


# ---------------------------------------------------------------------------
# query server
# ---------------------------------------------------------------------------

def test_server_interleaved_concurrent_requests(db):
    build, defaults = PARAM_QUERIES["q6"]
    b3, d3 = PARAM_QUERIES["q3"]
    oracle = VolcanoEngine(db)
    reqs = [
        (build(), defaults),
        (b3(), d3),
        (build(), dict(defaults, **ALT_BINDINGS["q6"])),
        (b3(), dict(d3, cutoff=days("1995-06-15"))),
        (build(), defaults),
    ]
    with QueryServer(db, preset("opt"), max_workers=4) as srv:
        results = srv.serve_batch([(p, dict(b)) for p, b in reqs])
        stats = srv.stats
        cache_stats = srv.cache.stats
    assert stats.completed == len(reqs) and stats.errors == 0
    assert cache_stats.compiles == 2      # one per distinct plan shape
    for (plan, bindings), got in zip(reqs, results):
        assert_matches(got, oracle.execute(plan, bindings))


def test_server_shares_one_inflight_compilation(db):
    """Two concurrent requests for the same plan shape: the second parks on
    the first's in-flight compilation; exactly one staging happens."""
    build, defaults = PARAM_QUERIES["q6"]
    gate, started = threading.Event(), threading.Event()

    def hook(_key):
        started.set()
        assert gate.wait(timeout=60)

    before = compile_mod.STAGINGS
    with QueryServer(db, preset("opt"), compile_hook=hook,
                     max_workers=4) as srv:
        f1 = srv.submit(build(), dict(defaults))
        assert started.wait(timeout=60)   # first request is now compiling
        f2 = srv.submit(build(), dict(defaults, **ALT_BINDINGS["q6"]))
        while srv.stats.shared_compiles == 0 and not f2.done():
            threading.Event().wait(0.01)  # let f2 reach the in-flight check
        gate.set()
        r1, r2 = f1.result(120), f2.result(120)
        assert srv.stats.shared_compiles == 1
        assert srv.cache.stats.compiles == 1
    assert compile_mod.STAGINGS - before == 1
    oracle = VolcanoEngine(db)
    assert_matches(r1, oracle.execute(build(), defaults))
    assert_matches(r2, oracle.execute(build(),
                                      dict(defaults, **ALT_BINDINGS["q6"])))


def test_close_under_load_resolves_every_future(db):
    """Satellite bugfix: close() racing open windows.  Submitters hammer
    the server while it closes mid-traffic; every future that `submit`
    returned must resolve (result or error) — a window popped by the
    flusher around the close, or one stranded undispatched, must be
    flushed or failed, never silently dropped."""
    build, defaults = PARAM_QUERIES["q6"]
    alt = dict(defaults, **ALT_BINDINGS["q6"])
    futs, futs_lock = [], threading.Lock()
    stop = threading.Event()

    srv = QueryServer(db, preset("opt"), max_workers=2,
                      window_s=0.002, max_batch=4)

    def hammer(i):
        b = defaults if i % 2 else alt
        while not stop.is_set():
            try:
                f = srv.submit(build(), dict(b))
            except RuntimeError:
                return            # server closed: expected once racing
            with futs_lock:
                futs.append(f)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    # let traffic build up, then close mid-flight
    threading.Event().wait(0.05)
    srv.close()
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    with futs_lock:
        taken = list(futs)
    assert taken, "no requests made it in before close"
    for f in taken:
        assert f.done(), "close() left a submitted future pending"
    resolved = sum(1 for f in taken
                   if f.exception(timeout=0) is None)
    # at least the pre-close traffic must have real results; the rest
    # must carry an error, not hang
    assert resolved > 0
