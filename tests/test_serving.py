"""Overload-hardened serving: admission control (budget, fairness,
priorities), deadlines, bounded retry of transient faults, the
degradation ladder, adaptive windows, close() grace accounting, the
in-flight-dedup failure path, submit/close races, and the seeded chaos
harness (every future resolves, retried transients succeed, ServerStats
balances exactly, zero oracle drift)."""
import threading
import time

import pytest

from repro.core import VolcanoEngine, preset
from repro.core import compile as compile_mod
from repro.relational.queries import (PARAM_ALT_BINDINGS as ALT_BINDINGS,
                                      PARAM_QUERIES)
from repro.serve.admission import (AdmissionController, DeadlineExceeded,
                                   Overloaded, RateEMA, TransientError)
from repro.serve.chaos import ChaosSchedule, run_chaos
from repro.serve.query_server import QueryServer
from test_queries import assert_same


def assert_matches(got, want):
    assert_same(got, want, sort_insensitive=True)


def _balanced(stats) -> bool:
    return stats.outstanding() == 0


# ---------------------------------------------------------------------------
# admission controller (pure unit tests, no db)
# ---------------------------------------------------------------------------

def test_admission_budget_and_fairness():
    adm = AdmissionController(budget=4, tenant_frac=0.5)
    adm.admit("a")
    adm.admit("a")
    with pytest.raises(Overloaded) as ei:       # tenant cap = ceil(.5*4) = 2
        adm.admit("a")
    assert ei.value.reason == "fairness" and ei.value.tenant == "a"
    adm.admit("b")
    adm.admit("b")                              # budget now full (4)
    with pytest.raises(Overloaded) as ei:
        adm.admit("c")
    assert ei.value.reason == "budget"
    # release frees both the budget and the tenant's share
    adm.release("a")
    adm.admit("a")
    assert adm.pending() == 4


def test_admission_priority_headroom_and_tenant_bypass():
    adm = AdmissionController(budget=4, tenant_frac=0.5, headroom=1)
    for _ in range(2):
        adm.admit("a")
    # priority bypasses the tenant cap while the budget has room
    adm.admit("a", priority=1)
    adm.admit("b")
    # budget full: normal traffic rejected, priority uses the headroom
    with pytest.raises(Overloaded):
        adm.admit("b")
    adm.admit("b", priority=1)
    with pytest.raises(Overloaded):             # headroom exhausted too
        adm.admit("c", priority=1)
    assert adm.pending() == 5


def test_admission_anonymous_exempt_from_tenant_cap():
    adm = AdmissionController(budget=4, tenant_frac=0.5)
    for _ in range(4):
        adm.admit(None)                         # bounded only by the budget
    with pytest.raises(Overloaded) as ei:
        adm.admit(None)
    assert ei.value.reason == "budget"


def test_rate_ema_tracks_arrival_interval():
    ema = RateEMA()
    t = 0.0
    for _ in range(50):
        ema.observe(t)
        t += 0.01
    assert ema.interval() == pytest.approx(0.01, rel=1e-6)
    assert ema.rate() == pytest.approx(100.0, rel=1e-6)


def test_chaos_schedule_replays_from_seed():
    a, b = ChaosSchedule.seeded(5), ChaosSchedule.seeded(5)
    assert (a.compile_fails, a.exec_faults, a.slows) == \
        (b.compile_fails, b.exec_faults, b.slows)
    c = ChaosSchedule.seeded(6)
    assert (a.compile_fails, a.exec_faults, a.slows) != \
        (c.compile_fails, c.exec_faults, c.slows)


# ---------------------------------------------------------------------------
# server behaviors (db-backed)
# ---------------------------------------------------------------------------

def test_adaptive_window_scales_with_arrival_rate(db):
    with QueryServer(db, preset("opt"), window_s=0.0025,
                     max_batch=64) as srv:
        # dense traffic: window ≈ time for a full batch to arrive
        t = 0.0
        for _ in range(50):
            srv._arrivals.observe(t)
            t += 1e-5
        dense = srv._window_len(0)
        assert dense == pytest.approx(64e-5, rel=1e-6)
        # sparse traffic: clamped at 4x the base window
        srv._arrivals = type(srv._arrivals)()
        t = 0.0
        for _ in range(50):
            srv._arrivals.observe(t)
            t += 0.1
        sparse = srv._window_len(0)
        assert sparse == pytest.approx(4 * 0.0025, rel=1e-6)
        # overload rung shrinks both the window and the batch cap
        assert srv._window_len(1) == pytest.approx(sparse / 4, rel=1e-6)
        assert srv._batch_cap(1) == 16 and srv._batch_cap(0) == 64


def test_deadline_miss_fails_typed_without_poisoning_group(db):
    build, defaults = PARAM_QUERIES["q6"]
    with QueryServer(db, preset("opt"), window_s=0.25, max_batch=64,
                     adaptive_window=False) as srv:
        dead = srv.submit(build(), dict(defaults), timeout_s=0.02)
        live = srv.submit(build(), dict(defaults,
                                        **ALT_BINDINGS["q6"]))
        # same window: the flusher dispatches at ~0.25 s, far past the
        # first request's deadline — it must fail alone, typed
        with pytest.raises(DeadlineExceeded):
            dead.result(timeout=60)
        assert_matches(live.result(timeout=60),
                       VolcanoEngine(db).execute(
                           build(), dict(defaults, **ALT_BINDINGS["q6"])))
        srv.drain()
        st = srv.stats
    assert st.deadline_misses == 1
    assert st.errors == 1 and st.completed == 1
    assert _balanced(st)


def test_transient_fault_retried_and_succeeds(db):
    build, defaults = PARAM_QUERIES["q6"]
    calls = []

    def exec_hook(key, attempt):
        calls.append(attempt)
        if len(calls) == 1:
            raise TransientError("injected")

    with QueryServer(db, preset("opt"), exec_hook=exec_hook,
                     window_s=0.001, max_batch=4,
                     retry_backoff_s=0.001) as srv:
        fut = srv.submit(build(), dict(defaults))
        srv.flush()
        got = fut.result(timeout=120)
        st = srv.stats
    assert_matches(got, VolcanoEngine(db).execute(build(), defaults))
    assert calls == [0, 1]            # one failed attempt, one replay
    assert st.retries == 1 and st.errors == 0 and st.completed == 1
    assert _balanced(st)


def test_non_transient_fault_not_retried(db):
    build, defaults = PARAM_QUERIES["q6"]

    def exec_hook(key, attempt):
        raise ValueError("poisoned batch")

    with QueryServer(db, preset("opt"), exec_hook=exec_hook,
                     window_s=0.001, max_batch=4) as srv:
        fut = srv.submit(build(), dict(defaults))
        srv.flush()
        with pytest.raises(ValueError):
            fut.result(timeout=120)
        st = srv.stats
    assert st.retries == 0 and st.errors == 1
    assert _balanced(st)


def test_degradation_ladder_sheds_then_rejects(db):
    """Deterministic walk up the ladder: gate execution so pending grows
    one request at a time; rungs fire off the pre-admission load
    (budget 8: shed_batch at load .5/.625, shed_plan at .75/.875, then
    reject), degraded requests run mask-only plans with identical
    results, and the gate release drains everything cleanly."""
    build, defaults = PARAM_QUERIES["q6"]
    gate = threading.Event()

    def exec_hook(key, attempt):
        assert gate.wait(timeout=120)

    srv = QueryServer(db, preset("opt"), exec_hook=exec_hook,
                      window_s=0.001, max_batch=1, max_workers=2,
                      budget=8, shed_batch_load=0.5, shed_plan_load=0.75)
    try:
        futs = [srv.submit(build(), dict(defaults)) for _ in range(8)]
        with pytest.raises(Overloaded):
            srv.submit(build(), dict(defaults))
        gate.set()
        want = VolcanoEngine(db).execute(build(), defaults)
        for f in futs:
            assert_matches(f.result(timeout=120), want)
    finally:
        gate.set()
        srv.close()
    st = srv.stats
    assert st.shed_batch == 2 and st.shed_plan == 2 and st.rejected == 1
    assert st.completed == 8 and st.errors == 0
    assert srv.cache.stats.degraded == 2
    # degraded settings key their own cache entries (mask-only twin)
    assert srv.cache.stats.compiles == 2
    assert _balanced(st)


def test_inflight_dedup_owner_compile_failure_hands_off(db):
    """Satellite regression: the owner's compile raises -> exactly one
    parked waiter becomes the new owner, recompiles, and the cache ends
    warm; the owner's own window fails with the compile error."""
    build, defaults = PARAM_QUERIES["q6"]
    started, release = threading.Event(), threading.Event()
    calls = []

    def hook(_key):
        calls.append(None)
        if len(calls) == 1:
            started.set()
            assert release.wait(timeout=120)
            raise RuntimeError("boom: owner compile failed")

    before = compile_mod.STAGINGS
    with QueryServer(db, preset("opt"), compile_hook=hook, max_batch=1,
                     window_s=0.001, max_workers=4) as srv:
        f1 = srv.submit(build(), dict(defaults))
        assert started.wait(timeout=120)        # owner inside its compile
        f2 = srv.submit(build(), dict(defaults, **ALT_BINDINGS["q6"]))
        while srv.stats.shared_compiles == 0 and not f2.done():
            time.sleep(0.01)                    # waiter parked on the event
        release.set()                           # owner now raises
        with pytest.raises(RuntimeError, match="boom"):
            f1.result(timeout=120)
        got = f2.result(timeout=120)            # waiter re-owned + compiled
        st, cst = srv.stats, srv.cache.stats
        # cache ends warm: a fresh request is a pure hit
        hits_before = srv.cache.stats.hits
        f3 = srv.submit(build(), dict(defaults))
        srv.flush()
        f3.result(timeout=120)
    assert_matches(got, VolcanoEngine(db).execute(
        build(), dict(defaults, **ALT_BINDINGS["q6"])))
    assert len(calls) == 2                      # one failed, one successful
    assert cst.compiles == 1                    # only the waiter's compile
    assert compile_mod.STAGINGS - before == 1
    assert st.shared_compiles == 1 and st.errors == 1
    assert srv.cache.stats.hits > hits_before


def test_submit_racing_close_raises_before_windowing(db):
    """Satellite: a submit whose _prepare straddles close() must raise at
    the post-prepare closed re-check — never window the request or leave
    a future pending."""
    build, defaults = PARAM_QUERIES["q6"]
    srv = QueryServer(db, preset("opt"))
    entered, closed = threading.Event(), threading.Event()
    real_prepare = srv.cache._prepare

    def stalled_prepare(*a, **kw):
        entered.set()
        assert closed.wait(timeout=120)
        return real_prepare(*a, **kw)

    srv.cache._prepare = stalled_prepare
    result = {}

    def racer():
        try:
            result["fut"] = srv.submit(build(), dict(defaults))
        except BaseException as e:
            result["exc"] = e

    t = threading.Thread(target=racer)
    t.start()
    assert entered.wait(timeout=120)
    srv.close()                   # closes while the submit is in _prepare
    closed.set()
    t.join(timeout=120)
    assert not t.is_alive()
    assert "fut" not in result
    assert isinstance(result["exc"], RuntimeError)
    assert "closed" in str(result["exc"])
    assert srv.stats.submitted == 0 and not srv._windows
    assert _balanced(srv.stats)


def test_close_timeout_knob_counts_grace_expired(db):
    """Satellite: the grace period is a constructor knob, and requests it
    strands are counted in grace_expired — not folded into errors."""
    build, defaults = PARAM_QUERIES["q6"]
    release = threading.Event()

    def exec_hook(key, attempt):
        assert release.wait(timeout=120)    # a stuck worker

    srv = QueryServer(db, preset("opt"), exec_hook=exec_hook,
                      window_s=0.001, max_batch=1, close_timeout_s=0.05)
    fut = srv.submit(build(), dict(defaults))
    srv.flush()
    t0 = time.monotonic()
    srv.close()
    # close() did not wait out the stuck worker
    assert time.monotonic() - t0 < 30
    assert fut.done(), "close() left the stranded future pending"
    with pytest.raises(RuntimeError, match="grace"):
        fut.result()
    st = srv.stats
    assert st.grace_expired == 1 and st.errors == 0
    assert _balanced(st)
    # unstick the worker and join it: its late settle of the already
    # grace-failed future must count nothing
    release.set()
    srv._pool.shutdown(wait=True)
    assert srv.stats.completed == 0 and srv.stats.grace_expired == 1
    assert _balanced(srv.stats)


# ---------------------------------------------------------------------------
# chaos harness (tier-1 acceptance)
# ---------------------------------------------------------------------------

def test_chaos_every_future_resolves_and_stats_balance(db):
    """Seeded chaos: injected compile failures, transient execution
    faults, slow executions, and a mid-window close.  Every submitted
    future resolves (result or typed error), every retried transient
    succeeds, ServerStats balances exactly, and completed results carry
    zero drift vs the Volcano oracle."""
    sched = ChaosSchedule(compile_fails={0}, exec_faults={1, 4},
                          slows={2, 6}, slow_s=0.005)
    report = run_chaos(db, seed=7, n_requests=32, schedule=sched,
                       close_mid_window=True, max_batch=4,
                       window_s=0.002, budget=64)
    st = report["stats"]
    assert report["all_resolved"], "a submitted future never resolved"
    assert report["balanced"], f"stats don't balance: {st}"
    assert st.outstanding() == 0
    assert report["oracle_drift"] == 0
    assert report["retried_ok"], \
        f"retries={st.retries} injected={report['injected']} " \
        f"outcomes={report['outcomes']}"
    # the schedule guarantees each fault family actually fired
    assert report["injected"]["compile_fail"] >= 1
    assert report["injected"]["exec_fault"] >= 1
    assert report["injected"]["slow"] >= 1
    # a compile fault fails its own window, typed
    assert report["outcomes"]["compile_fault"] >= 1
    assert st.errors >= report["outcomes"]["compile_fault"]


def test_chaos_seeded_schedule_run(db):
    """The rate-driven seeded schedule form: still fully resolved and
    balanced (fault counts vary with the seed, invariants must not)."""
    report = run_chaos(db, seed=11, n_requests=24,
                       close_mid_window=False, max_batch=4)
    assert report["all_resolved"] and report["balanced"]
    assert report["oracle_drift"] == 0 and report["retried_ok"]
