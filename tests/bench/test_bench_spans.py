"""`chipbench/spans.py`: the attribution of device idle to program spans
and of device time to operators, on synthetic traces, on a small trace
recorded on one TPU v5e (one round of pallas-scan at SF 1: q17, q6, q12,
q3 through `QueryServer`, with the compiled programs' instructions), and
the tool run end to end on the CPU."""
import json
import pathlib

import pytest

import _chipbench_path  # noqa: F401
from chipbench import spans

RECORDED = pathlib.Path(__file__).parent / "data" / "v5e_spans.json"
DEV = "/device:TPU:0"


def host(*events):
    """bench.window over [0, 100) and the given (start, dur, name, line)."""
    return [[0, 100, "bench.window", 0, {}]] + [[*e, {}] for e in events]


@pytest.mark.parametrize("name,events,ops,want", [
    # an idle piece goes to the shortest span covering it
    ("innermost", [(10, 80, "server.group", 1), (20, 30, "query.fetch", 1)],
     [[0, 20, "a", "m"], [50, 50, "b", "m"]],
     {"query.fetch": 30}),
    # spans on other threads count the same; a piece two threads cover
    # goes to the shorter span, whatever its thread
    ("cross_thread", [(10, 40, "server.tick_wait", 2),
                      (30, 60, "server.group", 1),
                      (40, 10, "query.dispatch", 1)],
     [[0, 10, "a", "m"], [90, 10, "b", "m"]],
     {"server.tick_wait": 30, "query.dispatch": 10, "server.group": 40}),
    # what no program span covers, or a benchmark span alone, is none
    ("none", [(0, 100, "bench.request q6", 0), (40, 20, "query.fetch", 1)],
     [[0, 10, "a", "m"]],
     {"none": 70, "query.fetch": 20}),
])
def test_idle_goes_to_the_innermost_covering_span(name, events, ops, want):
    sp = spans.reduce({"host": host(*events), "devices": {DEV: ops}})
    got = {k: round(v * 1e9) for k, v in sp.idle_by_span.items()}
    assert got == want
    assert sum(sp.idle_by_span.values()) == pytest.approx(
        sp.window_s - sp.busy_s)


def test_attribute_splits_an_interval_at_span_edges():
    got = spans.attribute([[0, 100]], [[10, 20, "query.dispatch"],
                                       [30, 40, "query.fetch"],
                                       [0, 100, "server.group"]])
    assert got == {"server.group": 40, "query.dispatch": 20,
                   "query.fetch": 40}
    assert spans.attribute([[5, 15]], []) == {"none": 10}


@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/op.sort/op.agg/op.scan/gather", "op.scan"),
    ("jit(fn)/op.sort/op.agg/scatter-add", "op.agg"),
    ("jit(fn_many)/vmap(jit(fn))/op.join/op.select/and", "op.select"),
    ("jit(fn)/op.sort/jit(lexsort)/iota", "op.sort"),
    ("jit(fn)/jit(clip)/min", spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_operator_is_the_innermost_scope(op_name, want):
    assert spans.operator(op_name) == want


HLO_A = """HloModule jit_fn, entry_computation_layout={...}
ENTRY %main.1 (p: s32[8]) -> f32[2] {
  %fusion.1 = s32[8]{0:T(1024)} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(fn)/op.agg/op.scan/gather" stack_frame_id=3}
  ROOT %fusion.2 = f32[2]{0:T(128)} fusion(%fusion.1), kind=kCustom, calls=%fc.2, metadata={op_name="jit(fn)/op.agg/scatter-add"}
}"""
HLO_B = """HloModule jit_fn, entry_computation_layout={...}
ENTRY %main.1 (p: s32[8]) -> f32[2] {
  %fusion.1 = pred[8]{0:T(1024)} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(fn)/op.select/gt"}
  %copy-start = (s32[8]{0}, s32[8]{0}, u32[]{:S(2)}) copy-start(%p)
}"""


def test_modules_match_their_program_and_name_operators():
    ops = [[0, 10, "%fusion.1 = pred[8]{0:T(1024)} fusion(s32[8]{0} %p), "
            "kind=kLoop, calls=%fc", "jit_fn(2)"],
           [10, 5, "%copy-start = (s32[8]{0}, s32[8]{0}, u32[]{:S(2)}) "
            "copy-start(s32[8]{0} %p)", "jit_fn(2)"],
           [20, 30, "%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8]{0} %p), "
            "kind=kLoop, calls=%fc", "jit_fn(1)"],
           [50, 40, "%fusion.2 = f32[2]{0:T(128)} fusion(s32[8]{0:T(1024)} "
            "%fusion.1), kind=kCustom, calls=%fc.2", "jit_fn(1)"],
           [90, 5, "%fusion.9 = f32[2]{0} fusion()", "jit_fn(3)"]]
    tr = {"host": host(), "devices": {DEV: ops}}
    programs = [spans.instructions(HLO_A), spans.instructions(HLO_B)]
    scopes = spans.match_modules(tr, programs)
    assert set(scopes) == {"jit_fn(1)", "jit_fn(2)"}   # 3 fits neither
    assert scopes["jit_fn(2)"]["%copy-start"] == ""
    sp = spans.reduce(tr, scopes)
    got = {k: round(v * 1e9) for k, v in sp.device_by_operator.items()}
    assert got == {"op.select": 10, spans.UNSCOPED: 5, "op.scan": 30,
                   "op.agg": 40, spans.UNMATCHED: 5}


def test_per_query_numbers():
    sp = spans.Spans(window_s=1.0, busy_s=0.5,
                     idle_by_span={"query.dispatch": 0.1,
                                   "query.fetch": 0.2, "none": 0.2},
                     device_by_operator={"op.scan": 0.3, "op.agg": 0.1},
                     host_by_span={"query.decode": 0.04})
    got = sp.per_query(10, window_wait_s=0.105, completed=10)
    assert got == pytest.approx({
        "window_wait_ms_per_query": 10.5, "dispatch_idle_ms_per_query": 10,
        "fetch_idle_ms_per_query": 20, "decode_ms_per_query": 4,
        "op_scan_ms_per_query": 30, "op_agg_ms_per_query": 10})
    # no device ops, no counter (a program without it): those are left out
    bare = spans.Spans(1.0, 0.0, {"none": 1.0}, {}, {})
    assert set(bare.per_query(10, None, 10)) == {"decode_ms_per_query"}
    assert bare.per_query(0, None, 0) == {}


def test_tool_reports_a_cpu_window(monkeypatch, tmp_path):
    """The tool end to end at SF 0.01: no device timeline on the CPU, so
    only the counter, the host spans and the idle attribution report."""
    import repro.core.persist as persist

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr(persist, "enable_compilation_cache", lambda: "off")
    line = spans.run("pallas-scan", 3, 1.0, require_tpu=False,
                     sf=_chipbench_path.SF, out=lambda msg: None)
    json.dumps(line)
    n = line["requests"]
    assert n > 0 and line["failed"] == 0
    m = line["metrics"]
    assert set(m) == {"window_wait_ms_per_query", "decode_ms_per_query"}
    # one client: every request waits out the whole 10 ms window
    assert m["window_wait_ms_per_query"] >= 9.99
    idle = line["idle_by_span"]
    assert idle["server.tick_wait"] >= 0.8 * n * 0.01
    assert {"query.dispatch", "query.fetch", "server.submit"} <= set(idle)
    assert line["none_share_of_idle"] < 10
    assert line["device_by_operator"] == {}


@pytest.fixture(scope="module")
def recorded():
    data = json.loads(RECORDED.read_text())
    programs = {q: {i: tuple(v) for i, v in p.items()}
                for q, p in data["programs"].items()}
    tr = data["trace"]
    return tr, programs, spans.match_modules(tr, list(programs.values()))


def test_recorded_modules_match_one_program_each(recorded):
    tr, programs, scopes = recorded
    modules = {e[3] for e in tr["devices"][DEV]}
    assert len(modules) == 4 and set(scopes) == modules
    # each module fits exactly one program, and no two modules the same
    fits = {m: [q for q, p in programs.items()
                if m in spans.match_modules(tr, [p])] for m in modules}
    assert sorted(q for qs in fits.values() for q in qs) == sorted(programs)
    assert all(len(qs) == 1 for qs in fits.values())


def test_recorded_idle_by_span_and_device_by_operator(recorded):
    tr, _, scopes = recorded
    sp = spans.reduce(tr, scopes)
    idle = sp.window_s - sp.busy_s
    assert sum(sp.idle_by_span.values()) == pytest.approx(idle)
    assert sp.idle_by_span["none"] < 0.1 * idle
    top = sorted(sp.idle_by_span, key=sp.idle_by_span.get)[-2:]
    assert set(top) == {"query.fetch", "server.tick_wait"}
    # four lone requests, each waiting out the 10 ms coalescing window
    assert 0.04 <= sp.idle_by_span["server.tick_wait"] <= 0.048
    ops = sp.device_by_operator
    assert sum(ops.values()) == pytest.approx(sp.busy_s, rel=0.01)
    assert spans.UNMATCHED not in ops and ops[spans.UNSCOPED] < 1e-3
    assert max(ops, key=ops.get) == "op.join"
    assert {"op.scan", "op.agg", "op.compact"} <= set(ops)
    per = sp.per_query(4, None, 4)
    assert per["fetch_idle_ms_per_query"] > per["dispatch_idle_ms_per_query"]


def test_recorded_spans_share_request_ids(recorded):
    tr, _, _ = recorded
    host = tr["host"]
    subs = [h for h in host if h[2] == "server.submit"]
    groups = [h for h in host if h[2] == "server.group"]
    assert len(subs) == len(groups) == 4
    assert sorted(str(h[4]["req"]) for h in subs) == \
        sorted(str(h[4]["reqs"]) for h in groups)
    for g in groups:
        inner = {h[2] for h in host if h[3] == g[3] and h is not g
                 and g[0] <= h[0] and h[0] + h[1] <= g[0] + g[1]}
        assert {"query.bind", "query.dispatch", "query.fetch",
                "query.decode", "server.settle"} <= inner
        assert not {h[3] for h in subs} & {g[3]}
