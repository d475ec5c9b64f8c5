"""Exchange (`core/operators/exchange.py`, the combines of agg and join):
megabytes each chip receives per request, from the result shapes of the
traced window's collective ops (`collectives.received_bytes`), averaged
over the chips.  0.0 when a one-chip trace holds none; nothing where a
trace over several chips holds none (`collectives.window_collectives`)."""

from chipbench.collectives import received_bytes, window_collectives


def read(w):
    ops = window_collectives(w)
    if ops is None:
        return None
    got = sum(received_bytes(name) for _, _, name in ops)
    return got / 1e6 / max(w.trace.n_devices, 1) / w.n_requests
