"""Device: share of the traced window in which no XLA op ran on the chip,
1 - union of op intervals / window (`trace.reduce`)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share
