"""Find the highest rate an open-loop cell sustains, once, on the chip.

    python3 chipbench/sweep.py --workload dash-opt --seed 5 --seconds 30 \
        --rates 2 3 4 5 6 8

One process: the cell's data and warm-up once, then the cell's mix offered
at each rate for `--seconds`.  Each rate prints one JSON line: requests
offered and answered, the 50th and 95th latency percentiles, p95 of the
window's first and second halves (a backlog that grows shows as a rising
second half), requests still open when the last arrival went out,
rejections and degraded plans.  A rate is sustained when nothing was
rejected or degraded and the second half's p95 is within 1.5x the
first's.  The cell's mix file then gets 4/5 of the highest sustained rate,
written in by hand with the sweep's lines in PERF.md.
"""
import argparse
import json
import os
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def sweep(workload: str, seed: int, seconds: float, rates: list,
          require_tpu: bool = True, sf: float | None = None, out=print,
          root=ROOT):
    from chipbench import harness

    lines = []
    with harness.serving(workload, require_tpu, sf, out,
                         root) as (_, mix, _, server):
        for rate in rates:
            before = harness.counters(server)
            recs, window_s, late = harness.measure_open(
                server, mix, seed, seconds, harness.spans(False), rate)
            delta = harness.diff(before, harness.counters(server))
            lat = np.array([r.latency for r in recs
                            if r.latency is not None]) * 1e3
            half = [np.array([r.latency for r in recs
                              if r.latency is not None
                              and (r.due < seconds / 2) == first]) * 1e3
                    for first in (True, False)]
            last_due = max(r.due for r in recs)
            open_at_end = sum(1 for r in recs if r.latency is None
                              or r.due + r.latency > last_due)
            p95 = [float(np.percentile(h, 95)) if h.size else None
                   for h in half]
            line = {"rate": rate, "offered": len(recs),
                    "answered": int(lat.size), "window_s": window_s,
                    "p50_ms": float(np.percentile(lat, 50)),
                    "p95_ms": float(np.percentile(lat, 95)),
                    "p95_first_half_ms": p95[0],
                    "p95_second_half_ms": p95[1],
                    "open_at_last_arrival": open_at_end,
                    "generator_late_s": late,
                    "rejected": delta["rejected"],
                    "shed_plan": delta["shed_plan"],
                    "requests_per_dispatch": delta["completed"]
                    / max(delta["batches"], 1),
                    "compiles": delta["stagings"] + delta["batch_traces"]}
            line["sustained"] = bool(
                not line["rejected"] and not line["shed_plan"]
                and line["answered"] == line["offered"]
                and p95[0] and p95[1] and p95[1] <= 1.5 * p95[0])
            out(json.dumps(line))
            lines.append(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench.harness import NoChip

    try:
        lines = sweep(args.workload, args.seed, args.seconds, args.rates)
    except NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    ok = [ln["rate"] for ln in lines if ln["sustained"]]
    print(json.dumps({"highest_sustained": max(ok) if ok else None,
                      "four_fifths": 0.8 * max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
