"""The comparison that decides `correct`: a served answer against the
reference's answer for the same query and bindings.

Two numbers per answer:

* `exact_ok` — the same columns and row count, and every integer and string
  column equal, row for row (rows keyed by their exact columns for the
  queries compared as sets);
* `gap` — the widest float gap of the answer: for every float value,
  |served - reference| / max(|reference|, 1e-6) / sqrt(rows), where rows is
  how many rows the reference summed into the value (its `#rows:` column,
  1 for a value that sums none).  A float32 sum's rounding grows with the
  rows it adds, about as their square root when the errors are random, so
  one limit holds a group of a million rows and a single price alike.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import rows_of

REL_FLOOR = 1e-6


def _order(cols: dict, exact: list) -> np.ndarray:
    n = len(next(iter(cols.values())))
    if not exact:
        return np.arange(n)
    return np.lexsort(tuple(np.asarray(cols[k]).astype(str)
                            if np.asarray(cols[k]).dtype.kind in "US"
                            else np.asarray(cols[k]) for k in
                            reversed(exact)))


def compare(got: dict, want: dict, as_set: bool) -> tuple[bool, float, str]:
    """(exact_ok, gap, what differed) for one served answer; `want` is the
    reference's answer with its `#rows:` columns."""
    want, rows = rows_of(want)
    if set(got) != set(want):
        return False, float("inf"), f"columns {sorted(got)} vs {sorted(want)}"
    lens = {len(np.asarray(v)) for v in got.values()} | {
        len(np.asarray(v)) for v in want.values()}
    if len(lens) != 1:
        return False, float("inf"), f"row counts {sorted(lens)}"
    floats = [k for k in want if np.asarray(want[k]).dtype.kind == "f"]
    exact = [k for k in want if k not in floats]
    g, w = got, want
    if as_set:
        order = _order(want, exact)
        g = {k: np.asarray(v)[_order(got, exact)] for k, v in got.items()}
        w = {k: np.asarray(v)[order] for k, v in want.items()}
        rows = {k: np.asarray(v)[order] for k, v in rows.items()}
    for k in exact:
        gv, wv = np.asarray(g[k]), np.asarray(w[k])
        if wv.dtype.kind in "US":
            same = np.array_equal(gv.astype(str), wv.astype(str))
        else:
            same = gv.dtype.kind in "iub" and np.array_equal(
                gv.astype(np.int64), wv.astype(np.int64))
        if not same:
            return False, float("inf"), f"column {k} differs"
    gap = 0.0
    for k in floats:
        gv = np.asarray(g[k], dtype=np.float64)
        wv = np.asarray(w[k], dtype=np.float64)
        if gv.size:
            n = np.maximum(np.asarray(rows.get(k, 1), np.float64), 1.0)
            err = (np.abs(gv - wv) / np.maximum(np.abs(wv), REL_FLOOR)
                   / np.sqrt(n))
            gap = max(gap, float(np.nan_to_num(err, nan=np.inf).max()))
    return True, gap, ""
