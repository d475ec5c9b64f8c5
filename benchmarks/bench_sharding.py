"""Sharded-execution scaling: the 1→N device curve (beyond paper).

Compiles each TPC-H query at ``Settings(shards=N)`` for N in {1, 2, 4, ...}
and records, per query and mesh size:

  * best wall-clock per execution (same protocol as bench_ladder),
  * per-shard rows scanned (partition-root block + routed-child blocks;
    replicated tables count in full — every shard holds them),
  * per-shard resident input bytes (sharded arrays split N ways,
    replicated arrays counted whole),
  * Exchange-node count of the lowered plan, next to the join count
    (the verifier's `exchange-count` rule bounds the former by the
    non-co-partitioned consumers during optimize()).

Mesh sizes are the powers of two up to the number of devices this
process sees (`jax.devices()`).  Everything runs in this one process: on
a TPU host a child process could not reach the chips its parent holds.
On a CPU, simulate devices by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run.

Writes ``BENCH_sharding.json`` (or $REPRO_BENCH_SHARD_OUT).  Scale
factor comes from $REPRO_SF like every other bench; the nightly scaling
run sets REPRO_SF=0.1.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

QUICK_KEEP = {"q1", "q3", "q6", "q12"}


def run() -> None:
    import jax

    from benchmarks.common import SF, csv, db, time_compiled
    from repro.core import CompiledQuery, preset
    from repro.core import ir
    from repro.core.passes.pipeline import optimize
    from repro.relational.queries import QUERIES

    d = db()
    n_dev = len(jax.devices())
    names = sorted(QUERIES)
    if os.environ.get("REPRO_QUICK") == "1":
        names = [q for q in names if q in QUICK_KEEP]
    meshes = [1 << i for i in range(n_dev.bit_length())]
    out: dict = {"sf": SF, "devices": n_dev, "queries": {}}
    for qname in names:
        rows = []
        for n in meshes:
            settings = dataclasses.replace(preset("opt"), shards=n)
            lowered = optimize(QUERIES[qname](), d, settings)
            nodes = list(ir.walk(lowered))
            n_ex = sum(isinstance(x, ir.Exchange) for x in nodes)
            n_join = sum(isinstance(x, ir.Join) for x in nodes)
            scanned = {x.table for x in nodes if isinstance(x, ir.Scan)}
            sp = d.shard_plan(n) if n > 1 else None
            shard_rows = sum(
                (sp.rows_per_shard(t)
                 if sp is not None and sp.part_of(t) is not None
                 else d.table(t).nrows)
                for t in scanned)
            cq = CompiledQuery(QUERIES[qname](), d, settings)
            shard_bytes = sum(
                v.nbytes // n if k in cq.sharded_keys else v.nbytes
                for k, v in cq.inputs.items())
            secs = time_compiled(cq)
            rows.append({
                "n_shards": n,
                "seconds": secs,
                "per_shard_rows": int(shard_rows),
                "per_shard_input_bytes": int(shard_bytes),
                "exchanges": n_ex,
                "joins": n_join,
            })
            print(csv(f"shard/{qname}/n{n}", secs,
                      f"rows={shard_rows};bytes={shard_bytes};"
                      f"exchanges={n_ex}"))
            sys.stdout.flush()
        out["queries"][qname] = rows
    path = os.environ.get("REPRO_BENCH_SHARD_OUT", "BENCH_sharding.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)


if __name__ == "__main__":
    run()
