"""End-to-end analytics driver (the paper's kind of system): load TPC-H,
stage + compile every query with the full optimization pipeline, execute,
and report per-query timings, memory and compile cost.

    PYTHONPATH=src python examples/tpch_analytics.py [--sf 0.05] [--config opt]
"""
import argparse
import time

from repro.core import CompiledQuery, enable_compilation_cache, preset
from repro.relational import Database
from repro.relational.queries import QUERIES


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--config", default="opt",
                    choices=["naive", "template", "tpch", "strdict", "opt",
                             "opt-pallas"])
    args = ap.parse_args()
    enable_compilation_cache()

    t0 = time.perf_counter()
    db = Database.tpch(sf=args.sf)
    print(f"load: {time.perf_counter() - t0:.2f}s  "
          f"({db.base_nbytes() / 1e6:.0f} MB)")

    print(f"{'query':<6} {'rows':>6} {'compile_ms':>11} {'exec_ms':>9} "
          f"{'mem_MB':>7}")
    for name, builder in sorted(QUERIES.items()):
        t0 = time.perf_counter()
        cq = CompiledQuery(builder(), db, preset(args.config))
        res = cq.run()                      # includes jit compile
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = cq.run()
        t_exec = time.perf_counter() - t0
        nrows = len(next(iter(res.values())))
        print(f"{name:<6} {nrows:>6} {t_compile * 1e3:>11.1f} "
              f"{t_exec * 1e3:>9.2f} {cq.input_nbytes() / 1e6:>7.1f}")


if __name__ == "__main__":
    main()
