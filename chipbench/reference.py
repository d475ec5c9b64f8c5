"""Plain reference for the TPC-H queries the benchmark sends: numpy over the
generated columns (`tpch_data.generate`), one function per query, written
from the SQL semantics of each query and importing nothing of the program.

Joins are gathers on the dense primary keys (checked when the data is
wrapped), group-bys are `np.unique` plus `np.bincount`.  Floating point
follows the configuration's stated precision: every float column, float
parameter and row-level arithmetic result is rounded by `rnd` (float32 for
the reference), and aggregates are accumulated in float64.  The control
passes a `rnd` that rounds to bfloat16 instead (`control.py`).

Each function takes the wrapped data, the query's bindings and `rnd`, and
returns `{column: np.ndarray}` in the query's output order.  Beside a float
column that sums rows, a column `#rows:<column>` holds how many rows each
value sums (`rows_of` takes them out; a float value without one sums one).
The comparison scales its float gaps by them (`compare.py`).
"""
from __future__ import annotations

import numpy as np

from chipbench.tpch_data import days

# answers whose row order is decided by a float sort key under a LIMIT:
# they are compared as sets of rows, keyed by their exact columns
SORT_INSENSITIVE = frozenset({"q3", "q10", "q18"})

# the spec's validation bindings of the parameterized templates
DEFAULTS = {
    "q1": {"shipdate_hi": days("1998-09-02")},
    "q3": {"cutoff": days("1995-03-15"), "segment": "BUILDING", "topn": 10},
    "q6": {"date_lo": days("1994-01-01"), "date_hi": days("1995-01-01"),
           "disc_lo": 0.05, "disc_hi": 0.07, "qty_max": 24.0},
    "q12": {"mode1": "MAIL", "mode2": "SHIP",
            "receipt_lo": days("1994-01-01"),
            "receipt_hi": days("1995-01-01")},
    "q14": {"ship_lo": days("1995-09-01"), "ship_hi": days("1995-10-01"),
            "promo_prefix": "PROMO"},
    "q19": {"brand1": "Brand#12", "qty1_lo": 1.0, "qty1_hi": 11.0,
            "brand2": "Brand#23", "qty2_lo": 10.0, "qty2_hi": 20.0,
            "brand3": "Brand#34", "qty3_lo": 20.0, "qty3_hi": 30.0},
}

_PKS = {"region": "r_regionkey", "nation": "n_nationkey",
        "supplier": "s_suppkey", "customer": "c_custkey",
        "part": "p_partkey", "orders": "o_orderkey"}


def f32(x):
    """The reference's rounding: float32, the configuration's precision."""
    return np.asarray(x, dtype=np.float32)


class Data:
    """Generated tables with column, string and word helpers."""

    def __init__(self, raw: dict):
        self.raw = raw
        for t, pk in _PKS.items():
            keys = raw[t].data[pk]
            if not np.array_equal(keys, np.arange(raw[t].nrows)):
                raise ValueError(f"{t}.{pk} is not the dense range 0..n-1")

    def col(self, t: str, c: str) -> np.ndarray:
        return self.raw[t].data[c]

    def strs(self, t: str, c: str) -> np.ndarray:
        return self.raw[t].vocabs[c][self.raw[t].data[c]]

    def has_word(self, t: str, c: str, word: str) -> np.ndarray:
        vocab = self.raw[t].word_vocabs[c]
        hit = np.flatnonzero(vocab == word)
        if hit.size == 0:
            return np.zeros(self.raw[t].nrows, dtype=bool)
        return (self.raw[t].data[c] == hit[0]).any(axis=1)


def _year(d: np.ndarray) -> np.ndarray:
    return d.astype("datetime64[D]").astype("datetime64[Y]").astype(
        np.int64) + 1970


def _group(*keys):
    """Group rows by the tuple of `keys`: (first row of each group,
    group index of every row, number of groups)."""
    combined = np.zeros(len(keys[0]), dtype=np.int64)
    for k in keys:
        _, code = np.unique(k, return_inverse=True)
        combined = combined * (int(code.max(initial=0)) + 1) + code
    _, first, inv = np.unique(combined, return_index=True,
                              return_inverse=True)
    return first, inv.reshape(-1), len(first)


def _sum(inv, n, v) -> np.ndarray:
    return np.bincount(inv, weights=np.asarray(v, np.float64), minlength=n)


def _count(inv, n) -> np.ndarray:
    return np.bincount(inv, minlength=n).astype(np.int64)


def _revenue(price, disc, rnd):
    return rnd(price * rnd(np.float32(1.0) - disc))


def _take(cols: dict, order) -> dict:
    return {k: v[order] for k, v in cols.items()}


ROWS = "#rows:"


def _rows(out: dict, rows, *cols) -> dict:
    """Record that each value of `cols` sums `rows` rows."""
    rows = np.atleast_1d(np.asarray(rows, np.int64))
    out.update({ROWS + c: rows for c in cols})
    return out


def q1(d: Data, p: dict, rnd) -> dict:
    m = d.col("lineitem", "l_shipdate") <= p["shipdate_hi"]
    qty = rnd(d.col("lineitem", "l_quantity")[m])
    price = rnd(d.col("lineitem", "l_extendedprice")[m])
    disc = rnd(d.col("lineitem", "l_discount")[m])
    tax = rnd(d.col("lineitem", "l_tax")[m])
    disc_price = _revenue(price, disc, rnd)
    charge = rnd(disc_price * rnd(np.float32(1.0) + tax))
    flag = d.strs("lineitem", "l_returnflag")[m]
    status = d.strs("lineitem", "l_linestatus")[m]
    first, inv, n = _group(flag, status)
    cnt = _count(inv, n)
    out = {"l_returnflag": flag[first], "l_linestatus": status[first],
           "sum_qty": _sum(inv, n, qty),
           "sum_base_price": _sum(inv, n, price),
           "sum_disc_price": _sum(inv, n, disc_price),
           "sum_charge": _sum(inv, n, charge),
           "avg_qty": _sum(inv, n, qty) / cnt,
           "avg_price": _sum(inv, n, price) / cnt,
           "avg_disc": _sum(inv, n, disc) / cnt,
           "count_order": cnt}
    _rows(out, cnt, "sum_qty", "sum_base_price", "sum_disc_price",
          "sum_charge", "avg_qty", "avg_price", "avg_disc")
    return _take(out, np.lexsort((out["l_linestatus"], out["l_returnflag"])))


def q3(d: Data, p: dict, rnd) -> dict:
    cut = p["cutoff"]
    lm = d.col("lineitem", "l_shipdate") > cut
    o = d.col("lineitem", "l_orderkey")[lm]
    om = d.col("orders", "o_orderdate") < cut
    cm = d.strs("customer", "c_mktsegment") == p["segment"]
    ok = om[o] & cm[d.col("orders", "o_custkey")[o]]
    o = o[ok]
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[lm][ok]),
                   rnd(d.col("lineitem", "l_discount")[lm][ok]), rnd)
    first, inv, n = _group(o)
    key = o[first]
    out = {"l_orderkey": key,
           "o_orderdate": d.col("orders", "o_orderdate")[key],
           "o_shippriority": d.col("orders", "o_shippriority")[key],
           "revenue": _sum(inv, n, rev)}
    _rows(out, _count(inv, n), "revenue")
    order = np.lexsort((out["o_orderdate"], -out["revenue"]))
    return _take(out, order[:int(p["topn"])])


def q4(d: Data, p: dict, rnd) -> dict:
    od = d.col("orders", "o_orderdate")
    om = (od >= days("1993-07-01")) & (od < days("1993-10-01"))
    late = d.col("lineitem", "l_commitdate") < d.col("lineitem",
                                                      "l_receiptdate")
    exists = np.zeros(len(od), dtype=bool)
    exists[d.col("lineitem", "l_orderkey")[late]] = True
    prio = d.strs("orders", "o_orderpriority")[om & exists]
    first, inv, n = _group(prio)
    out = {"o_orderpriority": prio[first], "order_count": _count(inv, n)}
    return _take(out, np.argsort(out["o_orderpriority"], kind="stable"))


def q5(d: Data, p: dict, rnd) -> dict:
    o = d.col("lineitem", "l_orderkey")
    od = d.col("orders", "o_orderdate")[o]
    c = d.col("orders", "o_custkey")[o]
    s = d.col("lineitem", "l_suppkey")
    sn = d.col("supplier", "s_nationkey")[s]
    region = d.strs("region", "r_name")[d.col("nation", "n_regionkey")[sn]]
    m = ((od >= days("1994-01-01")) & (od < days("1995-01-01"))
         & (region == "ASIA") & (d.col("customer", "c_nationkey")[c] == sn))
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[m]),
                   rnd(d.col("lineitem", "l_discount")[m]), rnd)
    name = d.strs("nation", "n_name")[sn[m]]
    first, inv, n = _group(name)
    out = _rows({"n_name": name[first], "revenue": _sum(inv, n, rev)},
                _count(inv, n), "revenue")
    return _take(out, np.argsort(-out["revenue"], kind="stable"))


def q6(d: Data, p: dict, rnd) -> dict:
    sd = d.col("lineitem", "l_shipdate")
    disc = rnd(d.col("lineitem", "l_discount"))
    qty = rnd(d.col("lineitem", "l_quantity"))
    m = ((sd >= p["date_lo"]) & (sd < p["date_hi"])
         & (disc >= rnd(p["disc_lo"])) & (disc <= rnd(p["disc_hi"]))
         & (qty < rnd(p["qty_max"])))
    rev = rnd(rnd(d.col("lineitem", "l_extendedprice")[m]) * disc[m])
    return _rows({"revenue": np.array([np.sum(rev, dtype=np.float64)])},
                 rev.size, "revenue")


def q7(d: Data, p: dict, rnd) -> dict:
    sd = d.col("lineitem", "l_shipdate")
    m = (sd >= days("1995-01-01")) & (sd < days("1997-01-01"))
    o = d.col("lineitem", "l_orderkey")[m]
    c = d.col("orders", "o_custkey")[o]
    names = d.strs("nation", "n_name")
    supp = names[d.col("supplier", "s_nationkey")[
        d.col("lineitem", "l_suppkey")[m]]]
    cust = names[d.col("customer", "c_nationkey")[c]]
    pair = (((supp == "FRANCE") & (cust == "GERMANY"))
            | ((supp == "GERMANY") & (cust == "FRANCE")))
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[m][pair]),
                   rnd(d.col("lineitem", "l_discount")[m][pair]), rnd)
    supp, cust = supp[pair], cust[pair]
    y_off = _year(sd[m][pair]) - 1992
    first, inv, n = _group(supp, cust, y_off)
    out = {"supp_nation": supp[first], "cust_nation": cust[first],
           "y_off": y_off[first], "revenue": _sum(inv, n, rev),
           "l_year": y_off[first] + 1992}
    _rows(out, _count(inv, n), "revenue")
    return _take(out, np.lexsort((out["l_year"], out["cust_nation"],
                                  out["supp_nation"])))


def q9full(d: Data, p: dict, rnd) -> dict:
    green = d.has_word("part", "p_name", "green")
    pk = d.col("lineitem", "l_partkey")
    m = green[pk]
    pk, sk = pk[m], d.col("lineitem", "l_suppkey")[m]
    # partsupp on its composite key (ps_partkey, ps_suppkey)
    n_supp = d.raw["supplier"].nrows
    ps_key = (d.col("partsupp", "ps_partkey").astype(np.int64) * n_supp
              + d.col("partsupp", "ps_suppkey"))
    order = np.argsort(ps_key, kind="stable")
    want = pk.astype(np.int64) * n_supp + sk
    pos = np.clip(np.searchsorted(ps_key[order], want), 0, len(order) - 1)
    hit = ps_key[order][pos] == want
    ps_row = order[pos][hit]
    pk, sk = pk[hit], sk[hit]
    price = rnd(d.col("lineitem", "l_extendedprice")[m][hit])
    disc = rnd(d.col("lineitem", "l_discount")[m][hit])
    qty = rnd(d.col("lineitem", "l_quantity")[m][hit])
    cost = rnd(d.col("partsupp", "ps_supplycost")[ps_row])
    profit = rnd(_revenue(price, disc, rnd) - rnd(cost * qty))
    nation = d.strs("nation", "n_name")[d.col("supplier", "s_nationkey")[sk]]
    o = d.col("lineitem", "l_orderkey")[m][hit]
    y_off = _year(d.col("orders", "o_orderdate")[o]) - 1992
    first, inv, n = _group(nation, y_off)
    out = {"n_name": nation[first], "y_off": y_off[first],
           "sum_profit": _sum(inv, n, profit),
           "o_year": y_off[first] + 1992}
    _rows(out, _count(inv, n), "sum_profit")
    return _take(out, np.lexsort((-out["o_year"], out["n_name"])))


def q10(d: Data, p: dict, rnd) -> dict:
    o = d.col("lineitem", "l_orderkey")
    od = d.col("orders", "o_orderdate")[o]
    m = ((d.strs("lineitem", "l_returnflag") == "R")
         & (od >= days("1993-10-01")) & (od < days("1994-01-01")))
    c = d.col("orders", "o_custkey")[o[m]]
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[m]),
                   rnd(d.col("lineitem", "l_discount")[m]), rnd)
    first, inv, n = _group(c)
    key = c[first]
    out = {"c_custkey": key,
           "c_acctbal": rnd(d.col("customer", "c_acctbal")[key]),
           "n_name": d.strs("nation", "n_name")[
               d.col("customer", "c_nationkey")[key]],
           "revenue": _sum(inv, n, rev)}
    _rows(out, _count(inv, n), "revenue")
    return _take(out, np.argsort(-out["revenue"], kind="stable")[:20])


def q12(d: Data, p: dict, rnd) -> dict:
    mode = d.strs("lineitem", "l_shipmode")
    rd = d.col("lineitem", "l_receiptdate")
    cd = d.col("lineitem", "l_commitdate")
    m = (np.isin(mode, [p["mode1"], p["mode2"]]) & (cd < rd)
         & (d.col("lineitem", "l_shipdate") < cd)
         & (rd >= p["receipt_lo"]) & (rd < p["receipt_hi"]))
    prio = d.strs("orders", "o_orderpriority")[
        d.col("lineitem", "l_orderkey")[m]]
    urgent = np.isin(prio, ["1-URGENT", "2-HIGH"])
    mode = mode[m]
    first, inv, n = _group(mode)
    out = {"l_shipmode": mode[first],
           "high_line_count": _sum(inv, n, urgent),
           "low_line_count": _sum(inv, n, ~urgent)}
    _rows(out, _count(inv, n), "high_line_count", "low_line_count")
    return _take(out, np.argsort(out["l_shipmode"], kind="stable"))


def q13(d: Data, p: dict, rnd) -> dict:
    special = (d.has_word("orders", "o_comment", "special")
               & d.has_word("orders", "o_comment", "requests"))
    per_cust = np.bincount(d.col("orders", "o_custkey")[~special],
                           minlength=d.raw["customer"].nrows)
    first, inv, n = _group(per_cust)
    out = {"c_count": per_cust[first], "custdist": _count(inv, n)}
    return _take(out, np.lexsort((-out["c_count"], -out["custdist"])))


def q14(d: Data, p: dict, rnd) -> dict:
    sd = d.col("lineitem", "l_shipdate")
    m = (sd >= p["ship_lo"]) & (sd < p["ship_hi"])
    ptype = d.strs("part", "p_type")[d.col("lineitem", "l_partkey")[m]]
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[m]),
                   rnd(d.col("lineitem", "l_discount")[m]), rnd)
    promo = np.char.startswith(ptype.astype(str), p["promo_prefix"])
    total = np.sum(rev, dtype=np.float64)
    return _rows({"promo_revenue": np.array(
        [100.0 * np.sum(rev[promo], dtype=np.float64) / total])},
        rev.size, "promo_revenue")


def q17(d: Data, p: dict, rnd) -> dict:
    pk = d.col("lineitem", "l_partkey")
    n_part = d.raw["part"].nrows
    qty = rnd(d.col("lineitem", "l_quantity"))
    avg = rnd(np.bincount(pk, weights=qty.astype(np.float64),
                          minlength=n_part)
              / np.maximum(np.bincount(pk, minlength=n_part), 1))
    part = ((d.strs("part", "p_brand") == "Brand#23")
            & (d.strs("part", "p_container") == "MED BOX"))
    m = part[pk] & (qty < rnd(np.float32(0.2) * avg[pk]))
    total = np.sum(rnd(d.col("lineitem", "l_extendedprice")[m]),
                   dtype=np.float64)
    return _rows({"avg_yearly": np.array([total / 7.0])}, m.sum(),
                 "avg_yearly")


def q18(d: Data, p: dict, rnd) -> dict:
    lines = np.bincount(d.col("lineitem", "l_orderkey"),
                        minlength=d.raw["orders"].nrows)
    sum_qty = np.bincount(d.col("lineitem", "l_orderkey"),
                          weights=rnd(d.col("lineitem", "l_quantity")),
                          minlength=d.raw["orders"].nrows)
    o = np.flatnonzero(sum_qty > 212.0)
    c = d.col("orders", "o_custkey")[o]
    out = {"c_name": d.strs("customer", "c_name")[c], "c_custkey": c,
           "o_orderkey": o, "o_orderdate": d.col("orders", "o_orderdate")[o],
           "o_totalprice": rnd(d.col("orders", "o_totalprice")[o]),
           "sum_qty": sum_qty[o]}
    _rows(out, lines[o], "sum_qty")
    order = np.lexsort((out["o_orderdate"], -out["o_totalprice"]))
    return _take(out, order[:100])


def q19(d: Data, p: dict, rnd) -> dict:
    m = (np.isin(d.strs("lineitem", "l_shipmode"), ["AIR", "REG AIR"])
         & (d.strs("lineitem", "l_shipinstruct") == "DELIVER IN PERSON"))
    pk = d.col("lineitem", "l_partkey")[m]
    brand = d.strs("part", "p_brand")[pk]
    cont = d.strs("part", "p_container")[pk]
    size = d.col("part", "p_size")[pk]
    qty = rnd(d.col("lineitem", "l_quantity")[m])
    keep = np.zeros(len(pk), dtype=bool)
    for i, prefix, max_size in ((1, "SM", 5), (2, "MED", 10),
                                (3, "LG", 15)):
        boxes = ([f"SM {b}" for b in ("CASE", "BOX", "PACK", "PKG")]
                 if prefix == "SM" else
                 [f"MED {b}" for b in ("BAG", "BOX", "PKG", "PACK")]
                 if prefix == "MED" else
                 [f"LG {b}" for b in ("CASE", "BOX", "PACK", "PKG")])
        keep |= ((brand == p[f"brand{i}"]) & np.isin(cont, boxes)
                 & (qty >= rnd(p[f"qty{i}_lo"]))
                 & (qty <= rnd(p[f"qty{i}_hi"]))
                 & (size >= 1) & (size <= max_size))
    rev = _revenue(rnd(d.col("lineitem", "l_extendedprice")[m][keep]),
                   rnd(d.col("lineitem", "l_discount")[m][keep]), rnd)
    return _rows({"revenue": np.array([np.sum(rev, dtype=np.float64)])},
                 rev.size, "revenue")


QUERIES = {"q1": q1, "q3": q3, "q4": q4, "q5": q5, "q6": q6, "q7": q7,
           "q9full": q9full, "q10": q10, "q12": q12, "q13": q13,
           "q14": q14, "q17": q17, "q18": q18, "q19": q19}


def answer(d: Data, query: str, bindings: dict | None = None,
           rnd=f32) -> dict:
    """The reference answer of `query` under `bindings` (the spec's
    validation values where none are given), with its `#rows:` columns."""
    params = dict(DEFAULTS.get(query, {}), **(bindings or {}))
    return QUERIES[query](d, params, rnd)


def rows_of(ans: dict) -> tuple[dict, dict]:
    """(the answer's columns, {column: rows each value sums}) of an
    answer with `#rows:` columns."""
    cols = {k: v for k, v in ans.items() if not k.startswith(ROWS)}
    rows = {k[len(ROWS):]: v for k, v in ans.items() if k.startswith(ROWS)}
    return cols, rows
