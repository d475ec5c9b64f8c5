"""Program spans (`repro/obs.py`) read back from a CPU profiler trace: a
served request's span tree and ids, a coalesced group, the window-wait
counter, and the per-operator named scopes in a staged program."""
import contextlib
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import obs
from repro.core import CompiledQuery, preset
from repro.relational.queries import (PARAM_ALT_BINDINGS, PARAM_QUERIES,
                                      QUERIES)
from repro.serve.query_server import QueryServer

PROGRAM = ("server.", "cache.", "query.")


def traced(tmp_path, fn) -> list:
    """Run `fn` under the profiler; the host events it recorded whose names
    start with `PROGRAM` or `test.`, as (start_ns, end_ns, name, line,
    stats), in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    out = [(e.start_ns, e.start_ns + e.duration_ns, e.name, i, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name == "/host:CPU"
           for i, line in enumerate(plane.lines) for e in line.events
           if e.name.startswith(PROGRAM + ("test.",))]
    return sorted(out)


def one(events, name):
    hits = [e for e in events if e[2] == name]
    assert len(hits) == 1, (name, hits)
    return hits[0]


def test_span_does_nothing_untraced():
    assert not TraceAnnotation.is_enabled()
    s = obs.span("query.fetch", req=1)
    assert isinstance(s, contextlib.nullcontext)
    assert s is obs.span("server.group", group=2, reqs="1 2")
    with s as entered:
        assert entered is None


def test_span_records_name_and_ids_when_traced(tmp_path):
    def work():
        with obs.span("test.outer", req=7, reqs="7 8"):
            with obs.span("test.inner"):
                pass

    ev = traced(tmp_path, work)
    outer, inner = one(ev, "test.outer"), one(ev, "test.inner")
    assert outer[4]["req"] == 7 and str(outer[4]["reqs"]) == "7 8"
    assert outer[3] == inner[3]                      # one thread's line
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


def test_served_request_spans_share_its_id(db, tmp_path):
    plan = QUERIES["q6"]
    with QueryServer(db, preset("opt")) as srv:
        srv.submit(plan()).result()          # compile outside the trace
        ev = traced(tmp_path, lambda: srv.submit(plan()).result())
    sub, grp = one(ev, "server.submit"), one(ev, "server.group")
    assert str(grp[4]["reqs"]) == str(sub[4]["req"])
    assert grp[3] != sub[3]                  # client thread, pool thread
    assert sub[1] <= grp[0]
    tick = one(ev, "server.tick_wait")       # the flusher, window open
    assert tick[3] not in (sub[3], grp[3])
    assert tick[0] <= grp[0]
    inner = [e for e in ev if e[3] == grp[3] and e is not grp]
    assert all(grp[0] <= e[0] and e[1] <= grp[1] for e in inner)
    order = ["cache.resolve", "query.bind", "query.dispatch", "query.fetch",
             "query.decode", "server.settle"]
    starts = [one(inner, name)[0] for name in order]
    assert starts == sorted(starts)


def test_coalesced_group_names_every_request(db, tmp_path):
    build, base = PARAM_QUERIES["q6"]
    alt = PARAM_ALT_BINDINGS["q6"]
    alts = alt if isinstance(alt, list) else [alt]
    batch = [(build(), {**base, **b}) for b in [{}] + alts + [{}]]
    with QueryServer(db, preset("opt")) as srv:
        srv.serve_batch(batch)               # compile outside the trace
        ev = traced(tmp_path, lambda: srv.serve_batch(batch))
    reqs = {str(e[4]["req"]) for e in ev if e[2] == "server.submit"}
    assert len(reqs) == len(batch)
    grp = one(ev, "server.group")
    assert set(str(grp[4]["reqs"]).split()) == reqs
    assert one(ev, "query.dispatch")[3] == grp[3]


def test_window_wait_covers_a_lone_requests_window(db):
    with QueryServer(db, preset("opt"), window_s=0.05,
                     adaptive_window=False) as srv:
        srv.submit(QUERIES["q6"]()).result()
        assert srv.stats.window_wait_s >= 0.05


def test_window_wait_near_zero_after_flush(db):
    with QueryServer(db, preset("opt"), window_s=5.0,
                     adaptive_window=False) as srv:
        srv.serve_batch([(QUERIES["q6"](), None)] * 2)
        assert srv.stats.completed == 2
        assert 0 <= srv.stats.window_wait_s < 0.5


@pytest.mark.parametrize("query,scopes", [
    ("q1", ("op.scan", "op.agg", "op.sort")),
    ("q3", ("op.scan", "op.join", "op.agg", "op.limit")),
])
def test_operators_scope_their_hlo(db, query, scopes):
    cq = CompiledQuery(QUERIES[query](), db, preset("opt"))
    text = jax.jit(cq.fn).lower(cq.bind()).as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}" in text
