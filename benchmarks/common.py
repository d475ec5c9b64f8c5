"""Shared benchmark helpers: timed query execution per engine config."""
from __future__ import annotations

import os
import time

from repro.core import CompiledQuery, VolcanoEngine, preset
from repro.core.persist import enable_compilation_cache
from repro.relational import Database
from repro.relational.queries import QUERIES

SF = float(os.environ.get("REPRO_SF", "0.05"))
REPEATS = int(os.environ.get("REPRO_REPEATS", "5"))

_DB = None


def db() -> Database:
    global _DB
    if _DB is None:
        enable_compilation_cache()
        _DB = Database.tpch(sf=SF)
    return _DB


def time_volcano(qname: str) -> float:
    eng = VolcanoEngine(db())
    times = []
    for _ in range(max(2, REPEATS // 2)):
        t0 = time.perf_counter()
        eng.execute(QUERIES[qname]())
        times.append(time.perf_counter() - t0)
    return min(times)


def compiled_query(qname: str, config: str) -> CompiledQuery:
    return CompiledQuery(QUERIES[qname](), db(), preset(config))


def time_compiled(cq: CompiledQuery) -> float:
    import jax

    out = cq._jitted(cq.inputs)           # warmup (compiles)
    jax.block_until_ready(out)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = cq._jitted(cq.inputs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return min(times)


def time_config(qname: str, config: str) -> float:
    if config == "dbx":
        return time_volcano(qname)
    return time_compiled(compiled_query(qname, config))


def csv(name: str, seconds: float, derived: str = "") -> str:
    return f"{name},{seconds * 1e6:.1f},{derived}"
