"""Plan cache (`core/plan_cache.py`): programs built inside the window.
Every `CompiledQuery` construction (`compile.STAGINGS`: cache misses,
re-plans, shrinks and overflow twins) plus every vmapped retrace
(`CacheStats.batch_traces`).  Warm-up is meant to leave none."""


def read(w):
    return float(w.counters["stagings"] + w.counters["batch_traces"])
