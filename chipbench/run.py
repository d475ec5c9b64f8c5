"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload power-opt --seed 7 --seconds 45 \
        --trace 0

The cell, its configuration and its traffic are found by name through
`BENCHMARK.json`.  The last line of standard output is one JSON object
(`correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` a `breakdown`; `checks` last, each number compared beside its
limit).  Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits non-zero.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# JAX reads its persistent cache's directory when it is imported: a fixed
# one inside the checkout, unless the environment names one
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        result = harness.run_cell(args.workload, abs(args.seed),
                                  args.seconds, bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
