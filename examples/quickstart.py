"""Quickstart: build a TPC-H database, run a query through the engine
ladder, and show the abstraction-without-regret effect.

    PYTHONPATH=src python examples/quickstart.py [--sf 0.02]
"""
import argparse
import time

from repro.core import (CompiledQuery, VolcanoEngine, enable_compilation_cache,
                        preset)
from repro.core.ir import plan_repr
from repro.relational import Database
from repro.relational.queries import q6, q12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.02)
    args = ap.parse_args()
    enable_compilation_cache()

    print(f"Generating TPC-H (sf={args.sf}) ...")
    db = Database.tpch(sf=args.sf)
    print(f"  lineitem rows: {db.table('lineitem').nrows:,}")

    print("\nQ12 logical plan:")
    print(plan_repr(q12()))

    print("\nInterpreted Volcano engine (the 'DBX' rung):")
    eng = VolcanoEngine(db)
    t0 = time.perf_counter()
    res = eng.execute(q12())
    t_volcano = time.perf_counter() - t0
    print(f"  {dict((k, v[:4]) for k, v in res.items())}")
    print(f"  time: {t_volcano * 1e3:.1f} ms")

    for config in ("naive", "opt"):
        cq = CompiledQuery(q12(), db, preset(config))
        cq.run()                     # warm up / compile
        t0 = time.perf_counter()
        res = cq.run()
        t = time.perf_counter() - t0
        print(f"\nStaged engine [{config}]:")
        print(plan_repr(cq.plan))
        print(f"  time: {t * 1e3:.1f} ms  "
              f"(speedup vs volcano: {t_volcano / t:.1f}x)")

    cq = CompiledQuery(q6(), db, preset("opt"))
    print("\nQ6 [opt] result:", cq.run())


if __name__ == "__main__":
    main()
