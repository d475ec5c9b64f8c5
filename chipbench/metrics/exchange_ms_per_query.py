"""Exchange (`core/operators/exchange.py`, the combines of agg and join):
device milliseconds of the traced window's collective ops, `all-gather`,
`all-reduce`, `reduce-scatter`, `collective-permute` and `all-to-all`
with their `-start` and `-done` halves, averaged over the chips, per
request.  0.0 when a one-chip trace holds none; nothing where a trace
over several chips holds none (`collectives.window_collectives`)."""

from chipbench.collectives import window_collectives


def read(w):
    ops = window_collectives(w)
    if ops is None:
        return None
    spent = sum(d for _, d, _ in ops)
    return spent * 1e-6 / max(w.trace.n_devices, 1) / w.n_requests
