"""Trace reduction: from a JAX profiler trace to the numbers the per-layer
readers and the result's `device` and `breakdown` keys take.

`load` flattens an `.xplane.pb` into plain lists (what a recorded test
trace holds); `reduce` works on those lists alone:

* device ops: the events of each TPU plane's "XLA Ops" line;
* host spans: the events of the host plane's lines (one per thread).  The
  benchmark's own `TraceAnnotation`s (`bench.window`,
  `bench.request <label>`, ...) land on the line of the thread that drives
  the window, beside JAX's own events there (`DevicePut`, ...).

Busy time is the union of a device's op intervals inside the `bench.window`
span, averaged over the devices; idle share is 1 - busy / window.  Idle gaps
are labelled by the benchmark span and the longest host event that cover
them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [[start_ns, dur_ns, name], ...]},
    "host": [[start_ns, dur_ns, name, thread], ...]} from the newest
    xplane file under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = out["devices"].setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[e.start_ns, e.duration_ns, e.name]
                            for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [[e.start_ns, e.duration_ns, e.name,
                                 line.name] for e in line.events]
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over devices
    n_devices: int
    ops: list                     # [start_ns, dur_ns, name] inside window
    gaps: list                    # [[label, seconds], ...] longest first
    top_ops: list                 # [[name, seconds], ...] most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_ops(self) -> list:
        return [e for e in self.ops if KERNEL_MARK in e[2]]


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def op_label(name: str) -> str:
    """A device op's label in the breakdown: its HLO instruction name, its
    result's shape and the first column it reads, e.g.
    `%fusion.5 f32[5917470] <- l_tax__.1`."""
    instr, _, rest = name.partition(" = ")
    shape = _SHAPE.search(rest)
    operand = re.search(r"%([A-Za-z_][\w.]*__[\w.]*)", rest)
    return " ".join([instr] + ([shape.group(0)] if shape else [])
                    + ([f"<- {operand.group(1)}"] if operand else []))


def _covering(spans: list, t: float, prefix: str) -> str | None:
    best = None
    for s, d, name, *_ in spans:
        if s <= t <= s + d and name.startswith(prefix):
            if best is None or d < best[1]:
                best = (s, d, name)
    return None if best is None else best[2]


def reduce(tr: dict, top: int = 10) -> Reduced:
    wins = [h for h in tr["host"] if h[2] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    w0, wd = wins[-1][0], wins[-1][1]
    w1 = w0 + wd
    # the driving thread's events: the benchmark's spans and JAX's own
    client = [h for h in tr["host"] if h[3:] == wins[-1][3:]]
    busy, ops, gaps = [], [], []
    for plane in sorted(tr["devices"]):
        evs = [e for e in tr["devices"][plane]
               if e[0] < w1 and e[0] + e[1] > w0]
        ops += evs
        merged = _union([[max(s, w0), min(s + d, w1)] for s, d, _ in evs])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    totals: dict = {}
    for _, d, name in ops:
        key = op_label(name)
        totals[key] = totals.get(key, 0.0) + d * 1e-9
    top_ops = sorted(([k, v] for k, v in totals.items()),
                     key=lambda kv: -kv[1])[:top]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        bench = _covering(client, mid, "bench.") or "no benchmark span"
        host = _covering([h for h in client
                          if not h[2].startswith("bench.")], mid, "")
        labelled.append([bench + (f" | {host}" if host else ""),
                         (e - s) * 1e-9])
    n = max(1, len(tr["devices"]))
    return Reduced(window_s=wd * 1e-9, busy_s=sum(busy) * 1e-9 / n,
                   n_devices=len(tr["devices"]), ops=ops, gaps=labelled,
                   top_ops=top_ops)


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|"
                    r"f64)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def kernel_bytes(name: str) -> int:
    """Bytes a kernel call must move at least: its results and operands, as
    the HLO instruction in the trace event's name gives their shapes."""
    lhs, _, rhs = name.partition(" custom-call(")
    results = lhs.split(" = ", 1)[-1]
    operands = rhs.split(", custom_call_target=", 1)[0]
    return _shape_bytes(results) + _shape_bytes(operands)
