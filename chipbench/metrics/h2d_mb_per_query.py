"""Staging and dispatch (`core/compile.py`): megabytes of host arrays handed
to the dispatch, per request.  One dispatch serves a coalesced group, so
the bytes are divided over the requests it answered
(`ServerStats.batches / completed`).  The device trace shows no transfer
events of its own, so this is counted from the compiled entries' inputs."""


def read(w):
    if not w.h2d_bytes or not w.counters.get("completed"):
        return None
    per_dispatch = w.counters["batches"] / w.counters["completed"]
    return sum(w.h2d_bytes) / len(w.h2d_bytes) * per_dispatch / 1e6
