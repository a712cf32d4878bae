"""Plain reference of the benchmark's 15 TPC-H queries, in numpy.

Each query is written out directly from its definition as the engine
states it (``compiler/tpch_ir.py``: the same predicates, constants and
output columns), over whole tables as ``bench/datagen.py`` makes them. It
imports nothing of the engine. ``run(qid, tables, ft)`` computes every
float in the dtype ``ft``: ``numpy.float64`` is the reference, and
``numpy.float32`` is the control that ``bench/control.py`` shows the
comparison to fail.

An answer is ``Answer(cols, ordered)``: the result columns, and whether
the query's final Sort or TopK makes the row order part of the answer.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import numpy as np

from bench.datagen import Tables, day

I64 = np.int64


@dataclasses.dataclass
class Answer:
    cols: Dict[str, np.ndarray]
    ordered: bool


class _Q:
    """One query's view of the tables, with float columns in ``ft``."""

    def __init__(self, tables: Tables, ft):
        self.t = tables
        self.ft = ft

    def col(self, table: str, name: str) -> np.ndarray:
        v = self.t[table][name]
        return v.astype(self.ft) if v.dtype.kind == "f" else v

    def lookup(self, table: str, key: str) -> np.ndarray:
        """Dense key -> row index over a table's unique integer key
        (-1 where absent)."""
        k = self.t[table][key].astype(I64)
        idx = np.full(int(k.max()) + 1, -1, I64)
        idx[k] = np.arange(len(k))
        return idx

    def revenue(self, m: np.ndarray) -> np.ndarray:
        e = self.col("lineitem", "l_extendedprice")[m]
        d = self.col("lineitem", "l_discount")[m]
        return e * (self.ft(1) - d)


def _probe(idx: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Row index of each key in a ``_Q.lookup`` table (-1 where absent)."""
    keys = keys.astype(I64)
    out = np.full(len(keys), -1, I64)
    inb = (keys >= 0) & (keys < len(idx))
    out[inb] = idx[keys[inb]]
    return out


def _where(pos: np.ndarray, row_mask: np.ndarray) -> np.ndarray:
    """``pos >= 0`` and the row at ``pos`` passes ``row_mask``."""
    ok = pos >= 0
    ok[ok] = row_mask[pos[ok]]
    return ok


class _Groups:
    """Rows grouped by integer keys, groups in ascending lexicographic key
    order; sums accumulate in row order in the values' own dtype."""

    def __init__(self, *keys: np.ndarray):
        ks = [k.astype(I64) for k in keys]
        rows = len(ks[0])
        code = np.zeros(rows, I64)
        mins, dims = [], []
        for k in ks:
            mn = int(k.min()) if rows else 0
            d = int(k.max()) - mn + 1 if rows else 1
            code = code * d + (k - mn)
            mins.append(mn)
            dims.append(d)
        self.order = np.argsort(code, kind="stable")
        sc = code[self.order]
        self.starts = np.flatnonzero(np.r_[True, sc[1:] != sc[:-1]]) \
            if rows else np.zeros(0, I64)
        self.n = len(self.starts)
        self.sizes = np.diff(np.r_[self.starts, rows]).astype(I64)
        self.inv = np.empty(rows, I64)
        self.inv[self.order] = np.repeat(np.arange(self.n), self.sizes)
        rem = sc[self.starts]
        cols = []
        for mn, d in zip(reversed(mins), reversed(dims)):
            cols.append(rem % d + mn)
            rem = rem // d
        self.keys = cols[::-1]

    def sum(self, vals: np.ndarray) -> np.ndarray:
        if self.n == 0:
            return np.zeros(0, vals.dtype)
        return np.add.reduceat(vals[self.order], self.starts)

    def count(self) -> np.ndarray:
        return self.sizes


def _top(order_by: np.ndarray, k: int) -> np.ndarray:
    return np.argsort(-order_by, kind="stable")[:k]


def _region(q: "_Q", regionkey: int) -> np.ndarray:
    """Nation keys of a region, as a mask over nation key values."""
    na = q.t["nation"]
    mask = np.zeros(int(na["n_nationkey"].max()) + 1, bool)
    mask[na["n_nationkey"][na["n_regionkey"] == regionkey]] = True
    return mask


def q1(q: _Q) -> Answer:
    li = q.t["lineitem"]
    m = li["l_shipdate"] <= day(1998, 8, 2) - 90
    e = q.col("lineitem", "l_extendedprice")[m]
    d = q.col("lineitem", "l_discount")[m]
    tax = q.col("lineitem", "l_tax")[m]
    g = _Groups(li["l_returnflag"][m], li["l_linestatus"][m])
    return Answer({
        "l_returnflag": g.keys[0], "l_linestatus": g.keys[1],
        "sum_qty": g.sum(q.col("lineitem", "l_quantity")[m]),
        "sum_base": g.sum(e),
        "sum_disc": g.sum(e * (q.ft(1) - d)),
        "sum_charge": g.sum(e * (q.ft(1) - d) * (q.ft(1) + tax)),
        "cnt": g.count()}, ordered=True)


def q3(q: _Q) -> Answer:
    D = day(1995, 3, 15)
    cu, od, li = q.t["customer"], q.t["orders"], q.t["lineitem"]
    ok_order = (od["o_orderdate"] < D) & _where(
        _probe(q.lookup("customer", "c_custkey"), od["o_custkey"]),
        cu["c_mktsegment"] == 1)
    m = li["l_shipdate"] > D
    hit = _where(_probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][m]),
                 ok_order)
    g = _Groups(li["l_orderkey"][m][hit])
    revenue = g.sum(q.revenue(m)[hit])
    top = _top(revenue, 10)
    opos = _probe(q.lookup("orders", "o_orderkey"), g.keys[0][top])
    return Answer({"l_orderkey": g.keys[0][top],
                   "o_orderdate": od["o_orderdate"][opos],
                   "o_shippriority": od["o_shippriority"][opos],
                   "revenue": revenue[top]}, ordered=True)


def q4(q: _Q) -> Answer:
    D = day(1993, 7, 1)
    od, li = q.t["orders"], q.t["lineitem"]
    lm = li["l_commitdate"] < li["l_receiptdate"]
    late = np.zeros(len(od["o_orderkey"]), bool)
    lpos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][lm])
    late[lpos[lpos >= 0]] = True
    m = (od["o_orderdate"] >= D) & (od["o_orderdate"] < D + 92) & late
    g = _Groups(od["o_orderpriority"][m])
    return Answer({"o_orderpriority": g.keys[0], "cnt": g.count()},
                  ordered=False)


def q5(q: _Q) -> Answer:
    D = day(1994, 1, 1)
    od, li, cu, su = (q.t[k] for k in ("orders", "lineitem", "customer",
                                       "supplier"))
    opos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"])
    hit = _where(opos, (od["o_orderdate"] >= D) & (od["o_orderdate"] < D + 365))
    cpos = _probe(q.lookup("customer", "c_custkey"), od["o_custkey"][opos[hit]])
    spos = _probe(q.lookup("supplier", "s_suppkey"), li["l_suppkey"][hit])
    keep = (cpos >= 0) & (spos >= 0)
    c_nat = cu["c_nationkey"][cpos]
    s_nat = su["s_nationkey"][spos]
    keep &= (c_nat == s_nat) & _region(q, 2)[s_nat]
    g = _Groups(s_nat[keep])
    revenue = g.sum(q.revenue(hit)[keep])
    order = np.argsort(revenue, kind="stable")[::-1]
    return Answer({"s_nationkey": g.keys[0][order], "revenue": revenue[order]},
                  ordered=True)


def q6(q: _Q) -> Answer:
    D = day(1994, 1, 1)
    li = q.t["lineitem"]
    disc = q.col("lineitem", "l_discount")
    m = ((li["l_shipdate"] >= D) & (li["l_shipdate"] < D + 365)
         & (disc >= 0.05) & (disc < 0.0701)
         & (q.col("lineitem", "l_quantity") < 24))
    e = q.col("lineitem", "l_extendedprice")[m]
    return Answer({"revenue": np.asarray([np.add.reduce(e * disc[m])])},
                  ordered=False)


def q7(q: _Q) -> Answer:
    d0, d1 = day(1995, 1, 1), day(1996, 12, 31)
    li, od, cu, su = (q.t[k] for k in ("lineitem", "orders", "customer",
                                       "supplier"))
    m = (li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1 + 1)
    spos = _probe(q.lookup("supplier", "s_suppkey"), li["l_suppkey"][m])
    opos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][m])
    cpos = _probe(q.lookup("customer", "c_custkey"), od["o_custkey"][opos])
    s_nat, c_nat = su["s_nationkey"][spos], cu["c_nationkey"][cpos]
    keep = (spos >= 0) & (opos >= 0) & (cpos >= 0) & (
        ((s_nat == 5) & (c_nat == 7)) | ((s_nat == 7) & (c_nat == 5)))
    g = _Groups(s_nat[keep], c_nat[keep], li["l_shipdate"][m][keep] // 365)
    return Answer({"s_nationkey": g.keys[0], "c_nationkey": g.keys[1],
                   "l_year": g.keys[2], "revenue": g.sum(q.revenue(m)[keep])},
                  ordered=True)


def q8(q: _Q) -> Answer:
    d0, d1 = day(1995, 1, 1), day(1996, 12, 31)
    li, od, cu, su, pa = (q.t[k] for k in (
        "lineitem", "orders", "customer", "supplier", "part"))
    m = _where(_probe(q.lookup("part", "p_partkey"), li["l_partkey"]),
               pa["p_type"] == 42)
    opos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][m])
    odate = od["o_orderdate"][opos]
    cpos = _probe(q.lookup("customer", "c_custkey"), od["o_custkey"][opos])
    spos = _probe(q.lookup("supplier", "s_suppkey"), li["l_suppkey"][m])
    keep = ((opos >= 0) & (cpos >= 0) & (spos >= 0)
            & (odate >= d0) & (odate < d1 + 1)
            & _region(q, 1)[cu["c_nationkey"][cpos]])
    vol = q.revenue(m)[keep]
    nat_vol = (su["s_nationkey"][spos][keep] == 3).astype(q.ft) * vol
    g = _Groups(odate[keep] // 365)
    nat, total = g.sum(nat_vol), g.sum(vol)
    return Answer({"o_year": g.keys[0],
                   "mkt_share": nat / np.maximum(total, q.ft(1e-9))},
                  ordered=False)


def q10(q: _Q) -> Answer:
    D = day(1993, 10, 1)
    li, od = q.t["lineitem"], q.t["orders"]
    m = li["l_returnflag"] == 2
    opos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][m])
    hit = _where(opos, (od["o_orderdate"] >= D) & (od["o_orderdate"] < D + 92))
    cust = od["o_custkey"][opos[hit]]
    known = _probe(q.lookup("customer", "c_custkey"), cust) >= 0
    g = _Groups(cust[known])
    revenue = g.sum(q.revenue(m)[hit][known])
    top = _top(revenue, 20)
    return Answer({"o_custkey": g.keys[0][top], "revenue": revenue[top]},
                  ordered=True)


def q12(q: _Q) -> Answer:
    D = day(1994, 1, 1)
    li, od = q.t["lineitem"], q.t["orders"]
    s, c, r = li["l_shipdate"], li["l_commitdate"], li["l_receiptdate"]
    m = (np.isin(li["l_shipmode"], (0, 4)) & (r >= D) & (r < D + 365)
         & (s < c) & (c < r))
    opos = _probe(q.lookup("orders", "o_orderkey"), li["l_orderkey"][m])
    hit = opos >= 0
    high = np.isin(od["o_orderpriority"][opos[hit]], (0, 1)).astype(I64)
    g = _Groups(li["l_shipmode"][m][hit])
    return Answer({"l_shipmode": g.keys[0], "high_cnt": g.sum(high),
                   "low_cnt": g.sum(1 - high)}, ordered=True)


def q14(q: _Q) -> Answer:
    D = day(1995, 9, 1)
    li, pa = q.t["lineitem"], q.t["part"]
    m = (li["l_shipdate"] >= D) & (li["l_shipdate"] < D + 30)
    ppos = _probe(q.lookup("part", "p_partkey"), li["l_partkey"][m])
    hit = ppos >= 0
    rev = q.revenue(m)[hit]
    promo = (pa["p_type"][ppos[hit]] < 15).astype(q.ft) * rev
    num, den = np.add.reduce(promo), np.add.reduce(rev)
    return Answer({"promo_revenue": np.asarray(
        [q.ft(100.0) * num / np.maximum(den, q.ft(1e-9))])}, ordered=False)


def q15(q: _Q) -> Answer:
    D = day(1996, 1, 1)
    li, su = q.t["lineitem"], q.t["supplier"]
    m = (li["l_shipdate"] >= D) & (li["l_shipdate"] < D + 92)
    g = _Groups(li["l_suppkey"][m])
    total = g.sum(q.revenue(m))
    best = total >= total.max() - 1e-9
    spos = _probe(q.lookup("supplier", "s_suppkey"), g.keys[0][best])
    found = spos >= 0
    return Answer({"l_suppkey": g.keys[0][best][found],
                   "total_rev": total[best][found],
                   "s_suppkey": su["s_suppkey"][spos[found]],
                   "s_nationkey": su["s_nationkey"][spos[found]]},
                  ordered=False)


def q17(q: _Q) -> Answer:
    li, pa = q.t["lineitem"], q.t["part"]
    m = _where(_probe(q.lookup("part", "p_partkey"), li["l_partkey"]),
               (pa["p_brand"] == 3) & (pa["p_container"] == 7))
    qty = q.col("lineitem", "l_quantity")[m]
    price = q.col("lineitem", "l_extendedprice")[m]
    g = _Groups(li["l_partkey"][m])
    avg = g.sum(qty) / np.maximum(g.count(), 1).astype(q.ft)
    small = qty < q.ft(0.2) * avg[g.inv]
    return Answer({"avg_yearly": np.asarray(
        [np.add.reduce(price[small]) / q.ft(7.0)])}, ordered=False)


def q18(q: _Q) -> Answer:
    li, od = q.t["lineitem"], q.t["orders"]
    g = _Groups(li["l_orderkey"])
    sum_qty = g.sum(q.col("lineitem", "l_quantity"))
    big = sum_qty > 150.0
    opos = _probe(q.lookup("orders", "o_orderkey"), g.keys[0][big])
    found = opos >= 0
    okey, sq, opos = g.keys[0][big][found], sum_qty[big][found], opos[found]
    price = q.col("orders", "o_totalprice")[opos]
    top = _top(price, 100)
    return Answer({"l_orderkey": okey[top], "sum_qty": sq[top],
                   "o_orderkey": od["o_orderkey"][opos[top]],
                   "o_custkey": od["o_custkey"][opos[top]],
                   "o_orderdate": od["o_orderdate"][opos[top]],
                   "o_totalprice": price[top]}, ordered=True)


def q19(q: _Q) -> Answer:
    li, pa = q.t["lineitem"], q.t["part"]
    qty = q.col("lineitem", "l_quantity")
    m = (np.isin(li["l_shipmode"], (0, 1)) & (li["l_shipinstruct"] == 2)
         & (((qty >= 1) & (qty < 12)) | ((qty >= 10) & (qty < 21))
            | ((qty >= 20) & (qty < 31))))
    ppos = _probe(q.lookup("part", "p_partkey"), li["l_partkey"][m])
    hit = ppos >= 0
    b, c, s = (pa[k][ppos[hit]] for k in ("p_brand", "p_container", "p_size"))
    lq = qty[m][hit]
    keep = (((b == 3) & (c < 10) & (lq < 12) & (s <= 5))
            | ((b == 5) & (c < 20) & (lq < 21) & (s <= 10))
            | ((b == 9) & (c < 40) & (lq < 31) & (s <= 15)))
    return Answer({"revenue": np.asarray(
        [np.add.reduce(q.revenue(m)[hit][keep])])}, ordered=False)


def q22(q: _Q) -> Answer:
    cu, od = q.t["customer"], q.t["orders"]
    bal = q.col("customer", "c_acctbal")
    m = (bal > 0.0) & np.isin(cu["c_nationkey"], (13, 17, 19, 21, 23))
    avg = bal[m].mean() if m.any() else q.ft(0)
    has_order = np.zeros(len(cu["c_custkey"]), bool)
    opos = _probe(q.lookup("customer", "c_custkey"), od["o_custkey"])
    has_order[opos[opos >= 0]] = True
    keep = m & (bal > avg) & ~has_order
    g = _Groups(cu["c_nationkey"][keep])
    return Answer({"c_nationkey": g.keys[0], "numcust": g.count(),
                   "totacctbal": g.sum(bal[keep])}, ordered=True)


QUERIES: Dict[str, Callable[[_Q], Answer]] = {
    f.__name__.upper(): f for f in (q1, q3, q4, q5, q6, q7, q8, q10, q12,
                                    q14, q15, q17, q18, q19, q22)}


def run(qid: str, tables: Tables, ft=np.float64) -> Answer:
    return QUERIES[qid](_Q(tables, ft))
