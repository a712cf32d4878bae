"""TPC-H tables for the benchmark, made from a configuration and a seed.

``generate`` follows the TPC-H specification's rules for the columns the
engine stores (TPC-H v3, clause 4.2.3): keys from 1, order keys sparse
(the first 8 of every 32), 1 to 7 lineitems per order written in order-key
order, customer keys of orders never a multiple of 3, each lineitem's
supplier one of its part's 4 ``partsupp`` suppliers, the extended price
from the part's retail price, the return flag and line status from the
dates against CURRENTDATE, and each order's total price from its lines.
Strings are dictionary codes in sorted order of the spec's strings (the
return flags A, N, R are 0, 1, 2). Values are drawn with numpy, not with
dbgen's generator, so the tables match dbgen's in shape and distribution
but not row for row. Like dbgen's, they are fixed by the configuration
(``data_seed``).

``load_catalog`` cuts the tables into partitions in that order through
the engine's ``Catalog.add_table``: ``rows_per_partition`` lineitem rows
each, every other table in ``dim_objects_per_node`` objects per storage
node, and, for a table the configuration's optional ``cluster`` map names,
boundaries aligned to runs of its key. The run's ``--seed`` places the
partitions on the storage nodes (``place``): every seed serves the same
rows in the same partitions, while the node that holds each partition,
and so each node's share of a query and the merge order, differ.
The configuration's optional ``key_skew`` map gives a Zipf exponent for a
foreign key (``l_partkey``, ``o_custkey``) in place of the spec's uniform
draw.

The reference (``bench/reference.py``) reads the arrays returned by
``generate``, never the catalog.
"""
from __future__ import annotations

import datetime
from typing import Dict, Optional

import numpy as np

EPOCH = datetime.date(1992, 1, 1)

Tables = Dict[str, Dict[str, np.ndarray]]

# rows per unit of scale factor (clause 4.2.5)
SF1_ROWS = dict(supplier=10_000, part=200_000, customer=150_000,
                orders=1_500_000)
PARTSUPP_PER_PART = 4
LINES_PER_ORDER = (1, 7)
# category counts: A/N/R, F/O, 7 ship modes, 4 ship instructions,
# 5 market segments, 5 order priorities, 25 brands (Brand#MN, M and N in
# 1..5), 150 types (6 x 5 x 5 syllables), 40 containers (5 x 8)
N_RETURNFLAG, N_LINESTATUS, N_SHIPMODE, N_SHIPINSTRUCT = 3, 2, 7, 4
N_MKTSEGMENT, N_ORDERPRIORITY, N_BRAND, N_TYPE, N_CONTAINER = 5, 5, 25, 150, 40
FLAG_A, FLAG_N, FLAG_R = 0, 1, 2
STATUS_F, STATUS_O = 0, 1
# nation key -> region key, the spec's fixed NATION table (clause 4.2.3)
NATION_REGION = (0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1)


def day(y: int, m: int, d: int) -> int:
    """Days since 1992-01-01, the engine's date encoding."""
    return (datetime.date(y, m, d) - EPOCH).days


STARTDATE = day(1992, 1, 1)
CURRENTDATE = day(1995, 6, 17)
ENDDATE = day(1998, 12, 31)


def order_keys(n: int) -> np.ndarray:
    """dbgen's sparse keys: of every 32 key values the first 8 are used."""
    i = np.arange(1, n + 1, dtype=np.int64)
    return ((i >> 3) << 5) + (i & 7)


def supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int
                ) -> np.ndarray:
    """The i-th (0..3) supplier of a part (clause 4.2.3, PS_SUPPKEY)."""
    pk = partkey.astype(np.int64)
    return (pk + i * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp + 1


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents (clause 4.2.3)."""
    pk = partkey.astype(np.int64)
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)


def draw_keys(rng: np.random.Generator, n_keys: int, size: int,
              zipf: Optional[float] = None) -> np.ndarray:
    """Keys 1..n_keys: uniform, or Zipf-distributed with exponent ``zipf``
    over key ranks in an order drawn from ``rng``."""
    if not zipf:
        return rng.integers(1, n_keys + 1, size, dtype=np.int64)
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(zipf)
    rank = np.searchsorted(np.cumsum(w / w.sum()), rng.random(size))
    return rng.permutation(n_keys)[np.minimum(rank, n_keys - 1)] + 1


def _money(rng, lo_cents: int, hi_cents: int, size: int) -> np.ndarray:
    return rng.integers(lo_cents, hi_cents + 1, size) / 100.0


def generate(sf: float, data_seed: int,
             key_skew: Optional[Dict[str, float]] = None) -> Tables:
    """The eight tables at TPC-H scale factor ``sf`` (1 is SF1: 1.5M
    orders, about 6M lineitems)."""
    skew = key_skew or {}
    rng = np.random.default_rng(data_seed)
    n = {k: max(int(round(v * sf)), 1) for k, v in SF1_ROWS.items()}
    n_supp = max(n["supplier"], 4)
    n["supplier"] = n_supp
    i32 = np.int32
    t: Tables = {}
    t["region"] = {"r_regionkey": np.arange(5, dtype=i32)}
    t["nation"] = {"n_nationkey": np.arange(25, dtype=i32),
                   "n_regionkey": np.asarray(NATION_REGION, i32)}
    t["supplier"] = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=i32),
        "s_nationkey": rng.integers(0, 25, n_supp).astype(i32),
        "s_acctbal": _money(rng, -99_999, 999_999, n_supp),
    }
    n_part = n["part"]
    partkey = np.arange(1, n_part + 1, dtype=np.int64)
    t["part"] = {
        "p_partkey": partkey.astype(i32),
        "p_brand": rng.integers(0, N_BRAND, n_part).astype(i32),
        "p_type": rng.integers(0, N_TYPE, n_part).astype(i32),
        "p_size": rng.integers(1, 51, n_part).astype(i32),
        "p_container": rng.integers(0, N_CONTAINER, n_part).astype(i32),
        "p_retailprice": retail_cents(partkey) / 100.0,
    }
    n_ps = n_part * PARTSUPP_PER_PART
    ps_part = np.repeat(partkey, PARTSUPP_PER_PART)
    t["partsupp"] = {
        "ps_partkey": ps_part.astype(i32),
        "ps_suppkey": supplier_of(
            ps_part, np.tile(np.arange(PARTSUPP_PER_PART), n_part),
            n_supp).astype(i32),
        "ps_availqty": rng.integers(1, 10_000, n_ps).astype(i32),
        "ps_supplycost": _money(rng, 100, 100_000, n_ps),
    }
    n_cust = n["customer"]
    t["customer"] = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=i32),
        "c_mktsegment": rng.integers(0, N_MKTSEGMENT, n_cust).astype(i32),
        "c_nationkey": rng.integers(0, 25, n_cust).astype(i32),
        "c_acctbal": _money(rng, -99_999, 999_999, n_cust),
    }

    n_ord = n["orders"]
    okey = order_keys(n_ord)
    # customers whose key is not a multiple of 3: the j-th is 3(j//2)+j%2+1
    j = draw_keys(rng, (2 * n_cust) // 3, n_ord, skew.get("o_custkey")) - 1
    o_date = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_ord)
    lines = rng.integers(LINES_PER_ORDER[0], LINES_PER_ORDER[1] + 1, n_ord)
    nl = int(lines.sum())
    l_okey = np.repeat(okey, lines)
    l_odate = np.repeat(o_date, lines)
    l_part = draw_keys(rng, n_part, nl, skew.get("l_partkey"))
    l_supp = supplier_of(l_part, rng.integers(0, PARTSUPP_PER_PART, nl),
                         n_supp)
    qty = rng.integers(1, 51, nl)
    ext_cents = qty * retail_cents(l_part)
    disc = rng.integers(0, 11, nl)         # hundredths
    tax = rng.integers(0, 9, nl)
    ship = l_odate + rng.integers(1, 122, nl)
    commit = l_odate + rng.integers(30, 91, nl)
    receipt = ship + rng.integers(1, 31, nl)
    flag = np.where(receipt <= CURRENTDATE,
                    np.where(rng.random(nl) < 0.5, FLAG_R, FLAG_A), FLAG_N)
    status = np.where(ship > CURRENTDATE, STATUS_O, STATUS_F)
    # O_TOTALPRICE: sum of extendedprice * (1 + tax) * (1 - discount) over
    # the order's lines, each line in whole cents
    line_total = np.rint(ext_cents * (100 + tax) * (100 - disc) / 1e4)
    starts = np.concatenate([[0], np.cumsum(lines)[:-1]])
    t["orders"] = {
        "o_orderkey": okey.astype(i32),
        "o_custkey": (3 * (j // 2) + j % 2 + 1).astype(i32),
        "o_orderdate": o_date.astype(i32),
        "o_orderpriority": rng.integers(0, N_ORDERPRIORITY,
                                        n_ord).astype(i32),
        "o_shippriority": np.zeros(n_ord, i32),
        "o_totalprice": np.add.reduceat(line_total, starts) / 100.0,
    }
    t["lineitem"] = {
        "l_orderkey": l_okey.astype(i32),
        "l_partkey": l_part.astype(i32),
        "l_suppkey": l_supp.astype(i32),
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": ext_cents / 100.0,
        "l_discount": disc / 100.0,
        "l_tax": tax / 100.0,
        "l_returnflag": flag.astype(i32),
        "l_linestatus": status.astype(i32),
        "l_shipdate": ship.astype(i32),
        "l_commitdate": commit.astype(i32),
        "l_receiptdate": receipt.astype(i32),
        "l_shipinstruct": rng.integers(0, N_SHIPINSTRUCT, nl).astype(i32),
        "l_shipmode": rng.integers(0, N_SHIPMODE, nl).astype(i32),
    }
    return t


def tables_for(config: Dict) -> Tables:
    return generate(config["tpch_scale_factor"], config["data_seed"],
                    config.get("key_skew"))


def place(catalog, seed: int) -> None:
    """Put each table's partitions on the storage nodes in an order drawn
    from ``seed``, as many on each node as round-robin would."""
    rng = np.random.default_rng(seed)
    nodes = catalog.nodes
    for node in nodes:
        node.partitions = []
    for parts in catalog.tables.values():
        for k, i in enumerate(rng.permutation(len(parts))):
            parts[i].node_id = nodes[k % len(nodes)].node_id
        for p in parts:
            nodes[p.node_id].partitions.append(p)


def load_catalog(tables: Tables, config: Dict, seed: int):
    """The engine's catalog over ``tables``, its partitions placed by
    ``seed``."""
    from repro.queryproc.table import ColumnTable
    from repro.storage.catalog import Catalog

    nodes = config["num_nodes"]
    cluster = config.get("cluster", {})
    cat = Catalog(nodes)
    for name, cols in tables.items():
        rows = len(next(iter(cols.values())))
        rpp = (config["rows_per_partition"] if name == "lineitem"
               else max(rows // (nodes * config["dim_objects_per_node"]), 1))
        cat.add_table(name, ColumnTable(dict(cols)), rpp,
                      cluster_key=cluster.get(name))
    place(cat, seed)
    return cat
