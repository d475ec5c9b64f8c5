"""The program's own spans and device time by operator, from one traced
window of a cell.

    python3 chipbench/spans.py --workload power-opt --seed 7 --seconds 45

One process: the cell's data and warm-up (`harness.serving`), then one
window of the cell's mix under the JAX profiler, as `run.py --trace 1`
serves it.  Prints one JSON line: the per-request numbers below,
`idle_by_span` (device-idle seconds by program span), `device_by_operator`
(device seconds by operator), `host_by_span` (host seconds by span name)
and the device's idle share.

What the program records (`src/repro/obs.py`): host spans named
`server.*`, `cache.*` and `query.*` on the threads that ran them, on the
profiler's clock, with request ids as stats; every physical operator's
HLO under `jax.named_scope("op.<operator>")`; and the counter
`ServerStats.window_wait_s`.

* Idle attribution.  Each idle interval of a device inside `bench.window`
  (between the union of its XLA ops) goes, piece by piece, to the
  innermost program span that covers it, on any thread: the shortest
  covering span; what no program span covers goes to `none`.
* Operators.  A v5e trace's `XLA Ops` events carry no `op_name` stat
  (device offset and duration only), so each op's operator comes from the
  compiled module's HLO text: the module run that holds the op (its
  `XLA Modules` event) is matched to the compiled program whose
  instructions carry the ops' names and result types, and the op takes the
  innermost `op.*` scope of that instruction's `op_name`, else
  `unscoped`; an op of a module no program matches counts as `unmatched`.
  The programs are compiled again after the window, with the persistent
  compilation cache off (`compiled_programs`).
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))

from chipbench import trace as tracing  # noqa: E402

PROGRAM = ("server.", "cache.", "query.")   # the program's span names
NONE = "none"                 # idle that no program span covers
UNSCOPED = "unscoped"         # an op whose op_name has no op.* scope
UNMATCHED = "unmatched"       # an op of a module no compiled program fits
_SCOPE = re.compile(r"(?:^|/)(op\.[a-z]+)(?=/|$)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*?) [a-z][\w-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [[start_ns, dur_ns, name, module], ...]},
    "host": [[start_ns, dur_ns, name, line, stats], ...]} from the newest
    xplane file under `trace_dir`: each device's XLA ops with the name of
    the module run that holds them, and the host events of the program
    and the benchmark (`bench.*`) with their host line's index and stats."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = out["devices"].setdefault(plane.name, [])
            for e in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = mods[i][2] if i >= 0 and \
                    e.start_ns < mods[i][1] else ""
                ops.append([e.start_ns, e.duration_ns, e.name, module])
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PROGRAM + ("bench.",)):
                        out["host"].append(
                            [e.start_ns, e.duration_ns, e.name, i,
                             {k: v if isinstance(v, (int, float)) else str(v)
                              for k, v in e.stats}])
    return out


def instructions(hlo_text: str) -> dict:
    """{instruction: (result type, op_name)} of a compiled module's text."""
    out = {}
    for ln in hlo_text.splitlines():
        m = _INSTR.match(ln)
        if m:
            op = _OP_NAME.search(ln)
            out[m.group(1)] = (m.group(2), op.group(1) if op else "")
    return out


def _head(op_name: str) -> tuple:
    """(instruction, result type) of a trace event's name."""
    m = _INSTR.match(op_name)
    return (m.group(1), m.group(2)) if m else (op_name, None)


def match_modules(tr: dict, programs: list) -> dict:
    """{module run name: {instruction: op_name}}: each module of the trace
    matched to the compiled program (`instructions` of its text) in which
    every op of the module finds its instruction with the same result
    type; none when no program fits them all."""
    seen: dict = {}
    for ops in tr["devices"].values():
        for _, _, name, module in ops:
            seen.setdefault(module, set()).add(_head(name))
    out = {}
    for module, heads in seen.items():
        for prog in programs:
            if all(prog.get(i, (None,))[0] == t for i, t in heads):
                out[module] = {i: prog[i][1] for i, _ in heads}
                break
    return out


def operator(op_name: str) -> str:
    """The innermost `op.*` scope of an `op_name` path, else unscoped."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def attribute(idle: list, spans: list) -> dict:
    """{span name: ns} of the idle intervals [[start, end], ...] (sorted,
    disjoint), each piece to the innermost (shortest) span of
    [[start, dur, name], ...] that covers it, or to `none`."""
    cuts = sorted({s for s, _, _ in spans} | {s + d for s, d, _ in spans})
    order = sorted(spans, key=lambda sp: sp[0])
    labels, active, j = [], [], 0
    for a in cuts[:-1]:
        while j < len(order) and order[j][0] <= a:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[0] + sp[1] > a]
        labels.append(min(active, key=lambda sp: sp[1])[2]
                      if active else NONE)
    out: dict = {}
    for a, b in idle:
        k = max(bisect.bisect_right(cuts, a) - 1, 0)
        covered = 0
        while k < len(labels) and cuts[k] < b:
            part = min(b, cuts[k + 1]) - max(a, cuts[k])
            if part > 0:
                out[labels[k]] = out.get(labels[k], 0) + part
                covered += part
            k += 1
        if b - a > covered:
            out[NONE] = out.get(NONE, 0) + (b - a - covered)
    return out


@dataclasses.dataclass
class Spans:
    window_s: float
    busy_s: float                 # mean over devices
    idle_by_span: dict            # {span name or none: s}, mean over devices
    device_by_operator: dict      # {op.* or unscoped/unmatched: s}, mean
    host_by_span: dict            # {span name: s} summed over threads

    def per_query(self, n_requests: int, window_wait_s: float | None,
                  completed: int) -> dict:
        """The per-request numbers, in ms; a number the run cannot give
        (no device ops, no counter, nothing completed) is left out."""
        out = {}
        if window_wait_s is not None and completed:
            out["window_wait_ms_per_query"] = window_wait_s * 1e3 / completed
        if not n_requests:
            return out
        ms = 1e3 / n_requests
        if self.busy_s > 0:
            out["dispatch_idle_ms_per_query"] = \
                self.idle_by_span.get("query.dispatch", 0.0) * ms
            out["fetch_idle_ms_per_query"] = \
                self.idle_by_span.get("query.fetch", 0.0) * ms
        out["decode_ms_per_query"] = \
            self.host_by_span.get("query.decode", 0.0) * ms
        if self.device_by_operator:
            out["op_scan_ms_per_query"] = \
                self.device_by_operator.get("op.scan", 0.0) * ms
            out["op_agg_ms_per_query"] = \
                self.device_by_operator.get("op.agg", 0.0) * ms
        return out


def reduce(tr: dict, scopes: dict | None = None) -> Spans:
    """Idle by span, device time by operator and host time by span over
    the last `bench.window` of a loaded trace; `scopes` is
    `match_modules`'s map (no operators without it)."""
    wins = [h for h in tr["host"] if h[2] == tracing.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {tracing.WINDOW_SPAN} span")
    w0, w1 = wins[-1][0], wins[-1][0] + wins[-1][1]
    prog = [[max(s, w0), min(s + d, w1) - max(s, w0), name]
            for s, d, name, *_ in tr["host"]
            if name.startswith(PROGRAM) and s < w1 and s + d > w0]
    host: dict = {}
    for _, d, name in prog:
        host[name] = host.get(name, 0.0) + d * 1e-9
    busy = 0.0
    idle: dict = {}
    ops: dict = {}
    devices = tr["devices"] or {"": []}
    for evs in devices.values():
        evs = [e for e in evs if e[0] < w1 and e[0] + e[1] > w0]
        merged = tracing._union([[max(e[0], w0), min(e[0] + e[1], w1)]
                                 for e in evs])
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, ns in attribute(gaps, prog).items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9
        if scopes is None:
            continue
        for s, d, name, module in evs:
            names = scopes.get(module)
            key = UNMATCHED if names is None \
                else operator(names.get(_head(name)[0], ""))
            ops[key] = ops.get(key, 0.0) + \
                (min(s + d, w1) - max(s, w0)) * 1e-9
    n = len(devices)
    return Spans(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
                 idle_by_span={k: v / n for k, v in idle.items()},
                 device_by_operator={k: v / n for k, v in ops.items()},
                 host_by_span=host)


def compiled_programs(server, reqs: list) -> list:
    """`instructions` of the compiled program each request kind runs,
    compiled anew for the device the server runs on.  The executable the
    server runs may carry the op_names of an older build of the same
    program: JAX's persistent cache leaves metadata out of its key, so it
    is off here, and the program is traced through a function of its own
    (`fn`, as the server's), so no in-memory cache hands back the
    server's executable.  The instructions come out the same."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chipbench.harness import plan_of

    out = []
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        for r in reqs:
            cq, runtime = server.cache.get(plan_of(r), server.settings,
                                           r.binding_dict())

            def fn(inputs, staged=cq.fn):
                return staged(inputs)

            lowered = jax.jit(fn).lower(cq.bind(runtime))
            out.append(instructions(lowered.compile().as_text()))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return out


def run(workload: str, seed: int, seconds: float, require_tpu: bool = True,
        sf: float | None = None, out=print, root=ROOT) -> dict:
    """One traced window of `workload`; returns the printed line."""
    import jax

    from chipbench import arrivals, harness

    with harness.serving(workload, require_tpu, sf, out,
                         root) as (_, mix, _, server):
        span = harness.spans(True)
        tdir = tempfile.mkdtemp(prefix="chipbench-spans-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                before = (server.stats.window_wait_s,
                          server.stats.completed)
                with span(tracing.WINDOW_SPAN):
                    if mix["loop"] == "closed":
                        recs, _ = harness.measure_closed(server, mix, seed,
                                                         seconds, span)
                    else:
                        recs, _, _ = harness.measure_open(server, mix, seed,
                                                          seconds, span)
                after = (server.stats.window_wait_s,
                         server.stats.completed)
            finally:
                jax.profiler.stop_trace()
            tr = load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        programs = compiled_programs(server, arrivals.kinds(mix))
    modules = {e[3] for ops in tr["devices"].values() for e in ops}
    scopes = match_modules(tr, programs)
    sp = reduce(tr, scopes)
    idle = sp.window_s - sp.busy_s
    return {"workload": workload, "seed": seed, "requests": len(recs),
            "failed": sum(1 for r in recs if r.error is not None),
            "metrics": sp.per_query(len(recs), after[0] - before[0],
                                    after[1] - before[1]),
            "device_idle_share": 100.0 * idle / sp.window_s,
            "none_share_of_idle": 100.0 * sp.idle_by_span.get(NONE, 0.0)
            / idle if idle > 0 else None,
            "modules_matched": [len(scopes), len(modules)],
            "idle_by_span": _ordered(sp.idle_by_span),
            "device_by_operator": _ordered(sp.device_by_operator),
            "host_by_span": _ordered(sp.host_by_span)}


def _ordered(d: dict) -> dict:
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench.harness import NoChip

    try:
        line = run(args.workload, abs(args.seed), args.seconds,
                   out=lambda msg: print(msg, file=sys.stderr, flush=True))
    except NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
