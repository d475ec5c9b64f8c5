"""Plan cache, compaction feedback: share of compacted executions in the
window whose capacity overflowed (`CacheStats.overflows / compactions`),
0 when no compacted program ran."""


def read(w):
    if not w.counters["compactions"]:
        return 0.0
    return 100.0 * w.counters["overflows"] / w.counters["compactions"]
