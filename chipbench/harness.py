"""One run of one cell: the configuration's data, a `QueryServer` over it,
warm-up of the cell's own shapes, a measured window of
`QueryServer.submit(...).result()` calls in the order the seed draws, then
the comparison of every answer with the reference.

`run_cell` returns the result line's object.  The order matters: the
window's device peak is read before the server is closed, and the reference
runs after that, on the host, outside `setup_s`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import arrivals, reference, trace as tracing
from chipbench.compare import compare
from chipbench.spec import ROOT, Spec

GRACE_S = 60.0          # how long past the window an answer may still come
WARM_PASSES = 12        # warm-up passes before giving up on convergence
# counters that move when something is built: warm-up repeats until a whole
# pass leaves them all unchanged
_BUILDS = ("stagings", "batch_traces", "replans", "shrinks")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def to_database(raw: dict):
    """The generated tables as the program's `Database`."""
    from repro.relational import Database, Table
    from repro.relational.schema import TPCH_SCHEMAS

    tables = {}
    for name, t in raw.items():
        tab = Table(TPCH_SCHEMAS[name], t.nrows, dict(t.data),
                    vocabs=dict(t.vocabs), word_vocabs=dict(t.word_vocabs))
        tab.compute_stats()
        tables[name] = tab
    return Database(tables)


def plan_of(req: arrivals.Request):
    from repro.relational.queries import PARAM_QUERIES, QUERIES

    return PARAM_QUERIES[req.query][0]() if req.template \
        else QUERIES[req.query]()


def counters(server) -> dict:
    from repro.core import compile as compile_mod

    s, c = server.stats, server.cache.stats
    return {"completed": s.completed, "batches": s.batches,
            "coalesced": s.coalesced, "errors": s.errors,
            "rejected": s.rejected, "shed_batch": s.shed_batch,
            "shed_plan": s.shed_plan, "compiles": c.compiles,
            "batch_traces": c.batch_traces, "compactions": c.compactions,
            "overflows": c.overflows, "replans": c.replans,
            "shrinks": c.shrinks, "stagings": compile_mod.STAGINGS}


def diff(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


@dataclasses.dataclass
class Record:
    req: arrivals.Request
    due: float                  # offset from the window's start, seconds
    latency: float | None       # seconds; None when no answer came
    answer: dict | None
    error: str | None = None


@dataclasses.dataclass
class Window:
    """What a per-layer reader reads: the `counters` moved over the window,
    the reduced trace (traced runs only), bytes handed to each request's
    dispatch, and the device's peaks."""
    seconds: float
    n_requests: int
    counters: dict
    trace: tracing.Reduced | None
    h2d_bytes: list
    peaks: dict


def spans(on: bool):
    """`TraceAnnotation` when tracing, else a span that does nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def warm_up(server, reqs: list, batches: list, out) -> int:
    """Serve every request kind one at a time, and each template's pool in
    coalesced groups of each size in `batches`, until a whole pass builds
    nothing.  Compaction feedback shrinks an entry after a streak of
    `compact_shrink_after` underused executions, so at least that many
    passes, and two more, come first.  Returns the passes made."""
    s = server.settings
    least = s.compact_shrink_after + 2 if s.compact_feedback else 2
    plans = {r: plan_of(r) for r in reqs}
    pools: dict = {}
    for r in reqs:
        if r.template:
            pools.setdefault(r.query, []).append(r)
    for n in range(1, WARM_PASSES + 1):
        before = counters(server)
        for r in reqs:
            server.submit(plans[r], r.binding_dict()).result()
        for size in batches:
            for pool in pools.values():
                group = [pool[i % len(pool)] for i in range(size)]
                server.serve_batch([(plans[r], r.binding_dict())
                                    for r in group])
        after = counters(server)
        if n >= least and all(before[k] == after[k] for k in _BUILDS):
            return n
    out(f"warm-up did not converge in {WARM_PASSES} passes: {after}")
    return WARM_PASSES


@contextlib.contextmanager
def serving(workload: str, require_tpu: bool = True,
            sf: float | None = None, out=print, root=ROOT):
    """A warmed `QueryServer` over a cell's data, for tools that serve the
    cell's mix many times in one process (`sweep.py`, `readings.py`).
    Yields (configuration, mix, generated tables, server)."""
    from chipbench.tpch_data import generate
    from repro.core import preset
    from repro.core.persist import enable_compilation_cache
    from repro.serve.query_server import QueryServer

    spec = Spec(root)
    wl = spec.workload(workload)
    cfg, mix = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    devices(wl["chips"], require_tpu)
    enable_compilation_cache()
    raw = generate(cfg["scale_factor"] if sf is None else sf,
                   cfg["data_seed"])
    with QueryServer(to_database(raw), preset(cfg["preset"]),
                     **cfg.get("server", {})) as server:
        warm_up(server, arrivals.kinds(mix), mix.get("warm_batches", []),
                out)
        yield cfg, mix, raw, server


def measure_closed(server, mix: dict, seed: int, seconds: float,
                   span) -> tuple[list, float]:
    """One client: the next request goes out when the last one answered.
    Whole rounds, until a round ends after `seconds`; the window ends at
    the last answer."""
    plans = {r: plan_of(r) for r in arrivals.kinds(mix)}
    recs: list = []
    start = time.perf_counter()
    for rnd in arrivals.closed_rounds(mix, seed):
        for r in rnd:
            with span(f"bench.request {r.label}"):
                t0 = time.perf_counter()
                try:
                    res = server.submit(plans[r], r.binding_dict()).result()
                    err = None
                except Exception as e:       # a failed request is counted
                    res, err = None, repr(e)
                t1 = time.perf_counter()
            recs.append(Record(r, t0 - start,
                               t1 - t0 if err is None else None, res, err))
        if t1 - start >= seconds:
            return recs, t1 - start


def measure_open(server, mix: dict, seed: int, seconds: float, span,
                 rate: float | None = None) -> tuple[list, float, float]:
    """Arrivals on the mix's schedule whatever the server does; latency
    from each request's due time.  Returns the records, the window (first
    due time to last answer) and how late the generator ran at most."""
    schedule = arrivals.open_schedule(mix, seed, seconds, rate)
    plans = {r: plan_of(r) for _, r in schedule}
    recs = [Record(r, due, None, None, "no answer")
            for due, r in schedule]
    ended = [0.0] * len(schedule)
    left = [len(schedule)]
    lock = threading.Lock()
    all_done = threading.Event()
    start = time.perf_counter()

    def finish(i, fut):
        now = time.perf_counter()
        rec = recs[i]
        try:
            rec.answer, rec.error = fut.result(), None
            rec.latency = now - (start + rec.due)
        except Exception as e:           # a failed request is counted
            rec.error = repr(e)
        ended[i] = now - start
        with lock:
            left[0] -= 1
            if not left[0]:
                all_done.set()

    late = 0.0
    with span("bench.submit"):
        for i, (due, r) in enumerate(schedule):
            delay = start + due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            late = max(late, time.perf_counter() - start - due)
            try:
                fut = server.submit(plans[r], r.binding_dict())
            except Exception as e:       # rejected at admission
                recs[i].error = repr(e)
                ended[i] = time.perf_counter() - start
                with lock:
                    left[0] -= 1
                    if not left[0]:
                        all_done.set()
                continue
            fut.add_done_callback(lambda f, i=i: finish(i, f))
    with span("bench.wait"):
        all_done.wait(timeout=max(0.0, start + seconds + GRACE_S
                                  - time.perf_counter()))
    return recs, max(ended) if any(ended) else seconds, late


def check(recs: list, raw: dict, limits: dict, rnd=reference.f32,
          want: dict | None = None) -> dict:
    """Compare every answer with the reference's answer for its query and
    bindings (kept in `want`, by request kind, when one is given).  Returns
    the numbers compared, the failed count (answers missing or wrong in an
    exact column), and each request kind's widest float gap."""
    data = reference.Data(raw)
    want = {} if want is None else want
    for rec in recs:
        if rec.req not in want:
            want[rec.req] = reference.answer(data, rec.req.query,
                                             rec.req.binding_dict(), rnd)
    unanswered = mismatched = 0
    gaps: dict = {}
    for rec in recs:
        if rec.answer is None:
            unanswered += 1
            continue
        ok, gap, _ = compare(rec.answer, want[rec.req],
                             rec.req.query in reference.SORT_INSENSITIVE)
        if not ok:
            mismatched += 1
            rec.error = "wrong"
            continue
        gaps[rec.req.label] = max(gaps.get(rec.req.label, 0.0), gap)
    return {"checks": {
        "unanswered": {"value": unanswered, "limit": 0},
        "exact_mismatches": {"value": mismatched, "limit": 0},
        "float_gap": {"value": max(gaps.values(), default=0.0),
                      "limit": limits["float_gap"]}},
        "failed": unanswered + mismatched, "gaps": gaps}


def host_bytes(server, settings, reqs: list) -> dict:
    """Bytes of host arrays each request kind's compiled entry hands to its
    dispatch (read after the window, from the warm cache)."""
    out = {}
    for r in set(reqs):
        cq, _ = server.cache.get(plan_of(r), settings, r.binding_dict())
        out[r] = sum(v.nbytes for v in cq.inputs.values()
                     if isinstance(v, np.ndarray))
    return out


def require_kernel(server, settings, reqs: list, mark: str) -> None:
    """Fail the run when a request's program lacks the kernel (`mark` in
    its lowered text): a kernel that fell back silently cannot pass."""
    import jax

    for r in reqs:
        cq, _ = server.cache.get(plan_of(r), settings, r.binding_dict())
        text = jax.jit(cq.fn).lower(cq.bind(r.binding_dict())).as_text()
        if mark not in text:
            raise RuntimeError(f"{r.label}: no {mark} in the program")


def e2e(recs: list, window_s: float, loop: str) -> dict:
    """The end-to-end metrics over every request of the window.  An open
    loop's geometric mean, timed from each request's due time, queues and
    spreads more than a closed loop's, and is a metric of its own."""
    lat = np.array([r.latency for r in recs if r.latency is not None])
    good = sum(1 for r in recs if r.error is None)
    out = {"queries_per_s": {"value": good / window_s, "unit": "queries/s"}}
    geomean = "latency_geomean_ms" + (".open" if loop == "open" else "")
    if lat.size:
        out[geomean] = {
            "value": float(math.exp(np.log(lat).mean()) * 1e3), "unit": "ms"}
        out["latency_p95_ms"] = {
            "value": float(np.percentile(lat, 95) * 1e3), "unit": "ms"}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, root=ROOT, require_tpu: bool = True,
             sf: float | None = None, rate: float | None = None,
             out=None) -> dict:
    """Run `workload` once and return the result line's object."""
    t0 = time.perf_counter() if t0 is None else t0
    out = out or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = Spec(root)
    wl = spec.workload(workload)
    cfg = spec.config(wl["config"])
    mix = spec.traffic(wl["traffic"])
    devs = devices(wl["chips"], require_tpu)
    kind = devs[0].device_kind
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    if require_tpu and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")

    from repro.core import preset
    from repro.core.persist import enable_compilation_cache
    from repro.serve.query_server import QueryServer

    enable_compilation_cache()
    from chipbench.tpch_data import generate

    scale = cfg["scale_factor"] if sf is None else sf
    raw = generate(scale, cfg["data_seed"])
    db = to_database(raw)
    settings = preset(cfg["preset"])
    reqs = arrivals.kinds(mix)
    server = QueryServer(db, settings, **cfg.get("server", {}))
    try:
        passes = warm_up(server, reqs, mix.get("warm_batches", []), out)
        if cfg.get("kernel") and devs[0].platform == "tpu":
            require_kernel(server, settings, reqs, cfg["kernel"])
        span = spans(trace)
        tdir = None
        if trace:
            import jax

            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        setup_s = time.perf_counter() - t0
        before = counters(server)
        late = 0.0
        with span(tracing.WINDOW_SPAN):
            if mix["loop"] == "closed":
                recs, window_s = measure_closed(server, mix, seed, seconds,
                                                span)
            else:
                recs, window_s, late = measure_open(server, mix, seed,
                                                    seconds, span, rate)
        after = counters(server)
        reduced = None
        if trace:
            import jax

            jax.profiler.stop_trace()
            reduced = tracing.reduce(tracing.load(tdir))
            shutil.rmtree(tdir, ignore_errors=True)
        stats = devs[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        nbytes = host_bytes(server, settings, [r.req for r in recs])
    finally:
        server.close()
    del server, db
    t_ref = time.perf_counter()
    verdict = check(recs, raw, cfg["limits"])
    out(f"reference and comparison: {time.perf_counter() - t_ref:.1f} s")
    delta = diff(before, after)
    window = Window(window_s, len(recs), delta, reduced,
                    [nbytes[r.req] for r in recs], peaks.get(kind, {}))
    if trace:
        metrics = {}
        for m in spec.metrics("per_layer", workload):
            value = spec.reader(m["name"])(window)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = e2e(recs, window_s, mix["loop"])
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        names = {m["name"] for m in spec.metrics("end_to_end", workload)}
        metrics = {k: v for k, v in metrics.items() if k in names}
    checks = verdict["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and verdict["failed"] == 0
    out(f"window: {len(recs)} requests in {window_s:.3f} s, generator "
        f"late by at most {late:.4f} s, warm-up passes {passes}, "
        f"counters {delta}")
    by_kind: dict = {}
    for r in recs:
        if r.latency is not None:
            by_kind.setdefault(r.req.query, []).append(r.latency * 1e3)
    out("latency ms by query (n, median, max): " + ", ".join(
        f"{q} {len(v)} {np.median(v):.1f} {max(v):.1f}"
        for q, v in sorted(by_kind.items())))
    widest = sorted(verdict["gaps"].items(), key=lambda kv: -kv[1])
    out(f"widest float gap of each request kind: {widest}")
    for name, c in checks.items():
        out(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": int(verdict["failed"]), "metrics": metrics,
              "device": {"platform": devs[0].platform, "kind": kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.gaps}
    result["checks"] = checks
    return result
