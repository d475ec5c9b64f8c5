"""End-to-end smoke run of the query engine on a TPU, checked against the
Volcano oracle.

    python chip_smoke.py [--seed 0]              # one chip, SF 1
    python chip_smoke.py --chips 4 [--seed 0]    # the sharded path only, SF 4

One chip, at TPC-H SF 1 generated from `--seed`:

  1. every `QUERIES` entry through a (non-tiered) `QueryServer` under the
     `opt` preset, one request at a time, then once more warm;
  2. every `PARAM_QUERIES` template with several bindings submitted
     together, so that one coalesced `run_many` window serves them;
  3. the `opt-pallas` rung on q3, q6, q12 and q17, whose compiled programs
     must contain the Pallas kernel (`tpu_custom_call`);
  4. every kernel under `repro.kernels` against its jnp oracle
     (`kernels/ref.py`) on the lineitem columns, compiled (not
     interpreted).

`--chips 4` runs only the sharded path: every `QUERIES` entry at
`shards=4` through a `QueryServer`, with the partitioned inputs checked to
span all four devices.

Every answer must equal the oracle's (`volcano.assert_same`).  Earlier
lines print smoke timings per query: set-up (first request minus second:
optimize, staging and XLA compile) and the second request's seconds.  The
last line is one JSON object naming the device.  Without a TPU, or when any
check fails, the script exits non-zero and prints no `"ok": true`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the opt-pallas rung and the kernel each query must route through
PALLAS_QUERIES = ("q3", "q6", "q12", "q17")


def _timed(server, plan, bindings=None):
    t0 = time.perf_counter()
    res = server.submit(plan, bindings).result()
    return res, time.perf_counter() - t0


def _check(name, got, want, failures, out):
    from repro.core.volcano import assert_same
    from repro.relational.queries import SORT_INSENSITIVE

    try:
        assert_same(got, want, name.split("/")[0] in SORT_INSENSITIVE)
    except AssertionError as e:
        failures.append(name)
        out(f"MISMATCH {name}: {str(e).strip()[:400]}")


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _same(name, got, want, failures, out, rtol=0.0, atol=0.0):
    import numpy as np

    try:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=atol)
    except AssertionError as e:
        failures.append(name)
        out(f"MISMATCH {name}: {str(e).strip()[:400]}")


def check_kernels(db, seed: int = 0, out=print) -> list:
    """Every Pallas kernel against its jnp oracle on the lineitem columns,
    with a q6-like predicate.  Aggregated values are integers whose
    per-group sums stay below 2**24, so every sum is exact in f32 and
    compared exactly.  Returns the names of the cases that differed."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.relational import days

    li = db.table("lineitem")
    n = li.nrows
    cols = {"d": jnp.asarray(li.col("l_shipdate")),
            "q": jnp.asarray(li.col("l_quantity"))}
    scalars = [jnp.int32(days("1994-01-01")), jnp.int32(days("1995-01-01")),
               jnp.float32(24.0)]

    def pred(c, s):
        return (c["d"] >= s[0]) & (c["d"] < s[1]) & (c["q"] < s[2])

    def vals(c, s):
        return [c["q"], c["q"] * (c["d"] % 7).astype(jnp.float32)]

    def gidx(c, s):
        return c["d"] % 64

    mask = pred(cols, scalars)
    g2048, v2 = cols["d"] % 2048, jnp.stack(vals(cols, scalars), 1)
    cap = 1 << 18
    widx, wcount = ref.compact_ref(mask, cap)
    wslot = ref.slot_of_ref(mask)
    failures: list = []
    cases = {
        "compact": (lambda: ops.compact(mask, cap), (widx, wcount)),
        "compact_translate": (lambda: ops.compact_translate(mask, cap),
                              (widx, wcount, wslot)),
        "compact_pred": (
            lambda: ops.compact_pred(cols, scalars, pred, cap,
                                     translate=True), (widx, wcount, wslot)),
        "filter_agg": (lambda: (ops.filter_agg(mask, g2048, v2, 2048),),
                       (ref.filter_agg_ref(mask, g2048, v2, 2048),)),
        "selective_filter_agg": (
            lambda: ops.selective_filter_agg(cols, scalars, pred, vals, gidx,
                                             2, 64, cap, True),
            ref.selective_filter_agg_ref(cols, scalars, pred, vals, gidx, 2,
                                         64, cap, True)),
    }
    fk = jnp.asarray(li.col("l_suppkey")) % 25
    table = jax.random.normal(jax.random.key(seed), (25, 3), jnp.float32)
    price = jnp.asarray(li.col("l_extendedprice"))
    cases["gather_join"] = (lambda: (ops.gather_join(fk, table),),
                            (ref.gather_join_ref(fk, table),))
    cases["masked_topk"] = (lambda: ops.masked_topk(price, mask, 10),
                            ref.masked_topk_ref(price, mask, 10))
    out(f"kernels vs oracle: {int(wcount)} of {n} rows pass the predicate, "
        f"compaction capacity {cap}")
    for name, (run, want) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(run())
        out(f"kernel {name}: {n} rows, {time.perf_counter() - t0:.3f} s "
            f"with compile")
        if len(got) != len(want):
            failures.append(f"kernel/{name}/arity")
            continue
        # the one-hot gather runs on the MXU: the kernel tests' tolerance
        tol = dict(rtol=1e-5, atol=1e-6) if name == "gather_join" else {}
        for i, (g, w) in enumerate(zip(got, want)):
            _same(f"kernel/{name}/{i}", g, w, failures, out, **tol)
    return failures


def smoke(sf: float = 1.0, seed: int = 0, out=print) -> dict:
    """The one-chip checks.  Returns a summary; `failures` lists every
    answer that differed from the oracle or kernel that did not fire."""
    import jax

    from repro.core import CompiledQuery, VolcanoEngine, preset
    from repro.kernels.ops import resolve_interpret
    from repro.relational import Database
    from repro.relational.queries import (PARAM_ALT_BINDINGS, PARAM_QUERIES,
                                          QUERIES)
    from repro.serve.query_server import QueryServer

    failures: list[str] = []
    t0 = time.perf_counter()
    db = Database.tpch(sf=sf, seed=seed)
    out(f"generated TPC-H sf={sf} seed={seed}: "
        f"{db.table('lineitem').nrows} lineitem rows, "
        f"{db.base_nbytes()} base bytes, {time.perf_counter() - t0:.2f} s")
    oracle = VolcanoEngine(db)
    timings = {}

    with QueryServer(db, preset("opt")) as server:
        # 1. every query, cold then warm, one request at a time
        for name, build in sorted(QUERIES.items()):
            _, first = _timed(server, build())
            got, second = _timed(server, build())
            timings[name] = {"setup_s": first - second, "exec_s": second}
            out(f"smoke timing {name}: setup_s={first - second:.3f} "
                f"exec_s={second:.4f}")
            _check(name, got, oracle.execute(build()), failures, out)
        # 2. each template, several bindings in one coalesced window
        coalesced = server.stats.coalesced
        for name, (build, defaults) in sorted(PARAM_QUERIES.items()):
            alt = dict(defaults, **PARAM_ALT_BINDINGS[name])
            bindings = [defaults, alt, alt, defaults]
            t1 = time.perf_counter()
            futs = [server.submit(build(), b) for b in bindings]
            server.flush()
            results = [f.result() for f in futs]
            out(f"smoke timing param/{name}: {len(bindings)} bindings "
                f"first_window_s={time.perf_counter() - t1:.3f}")
            for i, (b, got) in enumerate(zip(bindings, results)):
                _check(f"{name}/binding{i}", got,
                       oracle.execute(build(), params=b), failures, out)
        if server.stats.coalesced - coalesced < len(PARAM_QUERIES):
            failures.append("param/coalescing")
            out("no coalesced run_many window ran")
        if server.stats.shed_plan or server.stats.errors:
            failures.append("server/degraded")
            out(f"server degraded or failed: {server.stats}")

    # 3. the Pallas rung: the kernel must be in the compiled program
    compiled_kernels = not resolve_interpret(None)
    for name in PALLAS_QUERIES:
        t1 = time.perf_counter()
        cq = CompiledQuery(QUERIES[name](), db, preset("opt-pallas"))
        text = cq.compile().as_text()
        cq.run()
        first = time.perf_counter() - t1
        t1 = time.perf_counter()
        got = cq.run()
        second = time.perf_counter() - t1
        kernels = text.count("tpu_custom_call")
        timings[f"pallas/{name}"] = {"setup_s": first - second,
                                     "exec_s": second}
        out(f"smoke timing opt-pallas {name}: setup_s={first - second:.3f} "
            f"exec_s={second:.4f} tpu_custom_call={kernels}")
        if compiled_kernels and not kernels:
            failures.append(f"pallas/{name}/no-kernel")
        if cq.n_overflows:
            failures.append(f"pallas/{name}/overflow")
        _check(f"{name}/opt-pallas", got, oracle.execute(QUERIES[name]()),
               failures, out)

    # 4. every kernel against its jnp oracle
    failures += check_kernels(db, seed, out)

    peak = _peak_bytes(jax.devices()[0])
    out(f"device peak_bytes_in_use={peak}")
    return {"failures": failures, "timings": timings, "peak_bytes": peak}


def smoke_sharded(n: int, sf: float = 4.0, seed: int = 0,
                  out=print) -> dict:
    """The sharded path alone: every query at `shards=n`, checked against
    the oracle, with every partitioned input spread over `n` devices."""
    import jax

    from repro.core import VolcanoEngine, preset
    from repro.relational import Database
    from repro.relational.queries import QUERIES
    from repro.serve.query_server import QueryServer

    failures: list[str] = []
    devices = set(jax.devices()[:n])
    db = Database.tpch(sf=sf, seed=seed)
    out(f"generated TPC-H sf={sf} seed={seed}: "
        f"{db.table('lineitem').nrows} lineitem rows")
    settings = dataclasses.replace(preset("opt"), shards=n)
    oracle = VolcanoEngine(db)
    n_sharded = 0
    with QueryServer(db, settings, max_workers=8) as server:
        # all requests up front: the server's pool compiles them while
        # this thread computes the oracle's answers
        t0 = time.perf_counter()
        futs = {name: server.submit(build())
                for name, build in sorted(QUERIES.items())}
        want = {name: oracle.execute(build())
                for name, build in sorted(QUERIES.items())}
        for name, fut in futs.items():
            got = fut.result()
            out(f"smoke timing sharded {name}: "
                f"done_by_s={time.perf_counter() - t0:.3f}")
            _check(f"{name}/shards{n}", got, want[name], failures, out)
            cq, _ = server.cache.get(QUERIES[name](), settings)
            if cq.n_shards != n:
                failures.append(f"{name}/n_shards")
            for key in cq.sharded_keys:
                n_sharded += 1
                if cq.inputs[key].sharding.device_set != devices:
                    failures.append(f"{name}/{key}/placement")
    out(f"partitioned inputs checked: {n_sharded}")
    out("device peak_bytes_in_use="
        f"{[_peak_bytes(d) for d in sorted(devices, key=lambda d: d.id)]}")
    if not n_sharded:
        failures.append("no-partitioned-inputs")
    return {"failures": failures}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.core.persist import enable_compilation_cache

    print(f"compile cache: {enable_compilation_cache()}")
    if args.chips == 1:
        summary = smoke(1.0, args.seed)
    else:
        summary = smoke_sharded(args.chips, 4.0, args.seed)
    if summary["failures"]:
        print(f"chip_smoke FAILED: {summary['failures']}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
