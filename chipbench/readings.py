"""The readings that set the limits of `correct`, made on the chip at the
cell's own size: one process serves the cell's mix for a window under each
of many seeds and compares every answer (`harness.check`), then puts the
control (`control.py`, the reference in bfloat16) in the program's place
on the same requests.

    python3 chipbench/readings.py --workload power-opt --seconds 20 \
        --seeds 1001 1002 1003

One JSON line per seed: the numbers compared for the program and for the
control, and the program's widest gap of each request kind.  A number's
limit lies between the program's largest reading over a dozen seeds or
more and the control's smallest (PERF.md).
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def readings(workload: str, seeds: list, seconds: float,
             require_tpu: bool = True, sf: float | None = None, out=print,
             root=ROOT):
    from chipbench import arrivals, control, harness

    want: dict = {}
    lines = []
    with harness.serving(workload, require_tpu, sf, out,
                         root) as (cfg, mix, raw, server):
        ctrl = control.control_answers(raw, arrivals.kinds(mix))
        span = harness.spans(False)
        for seed in seeds:
            if mix["loop"] == "closed":
                recs, _ = harness.measure_closed(server, mix, seed, seconds,
                                                 span)
            else:
                recs, _, _ = harness.measure_open(server, mix, seed,
                                                  seconds, span)
            got = harness.check(recs, raw, cfg["limits"], want=want)
            swapped = [harness.Record(r.req, r.due, r.latency, ctrl[r.req])
                       for r in recs]
            ctl = harness.check(swapped, raw, cfg["limits"], want=want)
            line = {"workload": workload, "seed": seed,
                    "requests": len(recs),
                    "program": {k: c["value"]
                                for k, c in got["checks"].items()},
                    "control": {k: c["value"]
                                for k, c in ctl["checks"].items()},
                    "limits": {k: c["limit"]
                               for k, c in got["checks"].items()},
                    "gaps": dict(sorted(got["gaps"].items(),
                                        key=lambda kv: -kv[1]))}
            out(json.dumps(line))
            lines.append(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench.harness import NoChip

    try:
        readings(args.workload, args.seeds, args.seconds)
    except NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
