"""Data and plain reference of ``tpch-q4-sf1``. Imports nothing of the
program under test.

``generate`` draws ``orders`` and ``lineitem`` by the rules of TPC-H's
dbgen (spec 4.2.3) from the benchmark's seed: sparse order keys (8 of
every 32), 1 to 7 lineitems an order, ``o_orderdate`` uniform over
[1992-01-01, 1998-12-31 - 151 days], ship, commit and receipt dates from
the order date, five order priorities. Every column of the two tables is
drawn; strings are int32 surrogates, decimals integer cents (or
hundredths for the rates), dates days since 1970-01-01.

``reference`` is Q4 by plain numpy: the orders of the three months from
the request's start day (a slice of the orders sorted by date) that have
a lineitem committed before it was received (``np.isin``), counted by
priority (a bincount).
``control`` is the same with the counts in bfloat16, the precision below
the int32 the deployment counts in.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")
START_DATE = np.datetime64("1992-01-01", "D")
CURRENT_DATE = np.datetime64("1995-06-17", "D")
END_DATE = np.datetime64("1998-12-31", "D")
# the first start day a request draws; it draws n_start_days days on
FIRST_START = np.datetime64("1993-01-01", "D")
N_PRIORITIES = 5


def _day(d: np.datetime64) -> int:
    return int((d - EPOCH).astype(np.int64))


def _i32(a) -> np.ndarray:
    return np.asarray(a, dtype=np.int32)


def generate(sizes: dict, rng: np.random.Generator) -> dict:
    n = int(sizes["n_orders"])
    n_cust, n_part = int(sizes["n_customers"]), int(sizes["n_parts"])
    n_supp, n_clerk = int(sizes["n_suppliers"]), int(sizes["n_clerks"])
    i = np.arange(n, dtype=np.int64)
    o_orderkey = (i // 8) * 32 + i % 8 + 1
    # customer keys that are not multiples of 3 (dbgen leaves a third of
    # the customers without orders)
    k = rng.integers(0, n_cust * 2 // 3, n)
    o_custkey = k + k // 2 + 1
    o_orderdate = _day(START_DATE) + rng.integers(
        0, _day(END_DATE) - 151 - _day(START_DATE) + 1, n)
    o_orderpriority = rng.integers(0, N_PRIORITIES, n)
    o_clerk = rng.integers(1, n_clerk + 1, n)
    o_comment = rng.integers(0, 1 << 31, n)

    n_per = rng.integers(1, 8, n)
    n_lines = int(n_per.sum())
    starts = np.cumsum(n_per) - n_per
    l_orderkey = np.repeat(o_orderkey, n_per)
    l_linenumber = np.arange(n_lines) - np.repeat(starts, n_per) + 1
    odate = np.repeat(o_orderdate, n_per)
    l_partkey = rng.integers(1, n_part + 1, n_lines)
    # one of the part's four suppliers (dbgen's PS_SUPPKEY rule)
    corr = rng.integers(0, 4, n_lines)
    l_suppkey = (l_partkey + corr * (n_supp // 4 + (l_partkey - 1) // n_supp)) \
        % n_supp + 1
    l_quantity = rng.integers(1, 51, n_lines)
    retail = 90000 + (l_partkey // 10) % 20001 + 100 * (l_partkey % 1000)
    l_extendedprice = l_quantity * retail
    l_discount = rng.integers(0, 11, n_lines)
    l_tax = rng.integers(0, 9, n_lines)
    l_shipdate = odate + rng.integers(1, 122, n_lines)
    l_commitdate = odate + rng.integers(30, 91, n_lines)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n_lines)
    current = _day(CURRENT_DATE)
    # N(one), R(eturned), A(ccepted): R or A once received by CURRENTDATE
    l_returnflag = np.where(l_receiptdate <= current,
                            rng.integers(1, 3, n_lines), 0)
    l_linestatus = (l_shipdate > current).astype(np.int64)   # 0 F, 1 O
    l_shipinstruct = rng.integers(0, 4, n_lines)
    l_shipmode = rng.integers(0, 7, n_lines)
    l_comment = rng.integers(0, 1 << 31, n_lines)

    # F(ulfilled) when every line is F, O(pen) when every line is O, else P
    n_open = np.add.reduceat(l_linestatus, starts)
    o_orderstatus = np.where(n_open == 0, 0, np.where(n_open == n_per, 1, 2))
    line_total = l_extendedprice * (100 - l_discount) * (100 + l_tax) // 10000
    o_totalprice = np.add.reduceat(line_total, starts)

    orders = {
        "o_orderkey": o_orderkey, "o_custkey": o_custkey,
        "o_orderstatus": o_orderstatus, "o_totalprice": o_totalprice,
        "o_orderdate": o_orderdate, "o_orderpriority": o_orderpriority,
        "o_clerk": o_clerk, "o_shippriority": np.zeros(n, np.int64),
        "o_comment": o_comment,
    }
    lineitem = {
        "l_orderkey": l_orderkey, "l_partkey": l_partkey,
        "l_suppkey": l_suppkey, "l_linenumber": l_linenumber,
        "l_quantity": l_quantity, "l_extendedprice": l_extendedprice,
        "l_discount": l_discount, "l_tax": l_tax,
        "l_returnflag": l_returnflag, "l_linestatus": l_linestatus,
        "l_shipdate": l_shipdate, "l_commitdate": l_commitdate,
        "l_receiptdate": l_receiptdate, "l_shipinstruct": l_shipinstruct,
        "l_shipmode": l_shipmode, "l_comment": l_comment,
    }
    return {"orders": {c: _i32(v) for c, v in orders.items()},
            "lineitem": {c: _i32(v) for c, v in lineitem.items()}}


def window(start_offset: int):
    """The request's three months: [start, start + 3 months), days since
    1970-01-01; a day past the end of the target month is its last."""
    lo = FIRST_START + int(start_offset)
    month = lo.astype("datetime64[M]")
    target = month + 3
    last = (target + 1).astype("datetime64[D]") - 1
    hi = min(target.astype("datetime64[D]") + (lo - month.astype(
        "datetime64[D]")), last)
    return _day(lo), _day(hi)


def _by_date(columns: dict):
    """The orders in date order: their dates, and the priority of each
    that has a lineitem committed before it was received (-1 for the
    others). Built once a run; a request then reads a slice."""
    idx = columns.get("_by_date")
    if idx is None:
        o, li = columns["orders"], columns["lineitem"]
        late_keys = li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]
        late = np.isin(o["o_orderkey"], late_keys)
        order = np.argsort(o["o_orderdate"], kind="stable")
        idx = columns["_by_date"] = (
            o["o_orderdate"][order],
            np.where(late, o["o_orderpriority"], -1)[order])
    return idx


def _counts(columns: dict, params: dict) -> np.ndarray:
    lo, hi = window(params["start"][0])
    dates, priority = _by_date(columns)
    p = priority[np.searchsorted(dates, lo):np.searchsorted(dates, hi)]
    return np.bincount(p[p >= 0], minlength=N_PRIORITIES)


def _pairs(counts: np.ndarray) -> np.ndarray:
    keys = np.flatnonzero(counts)
    return np.stack([keys, counts[keys]], 1).reshape(-1).astype(np.float64)


def reference(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "Q4":
        raise KeyError(program)
    return _pairs(_counts(columns, params))


def control(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "Q4":
        raise KeyError(program)
    counts = _counts(columns, params)
    keys = np.flatnonzero(counts)
    low = counts[keys].astype(ml_dtypes.bfloat16).astype(np.float64)
    return np.stack([keys.astype(np.float64), low], 1).reshape(-1)


def answer(outputs: dict) -> np.ndarray:
    """The served answer, ``{priority: count}``, as the (priority, count)
    pairs in priority order, comparable with ``reference``."""
    counts = outputs["counts"] or {}
    return np.asarray([v for k in sorted(counts) for v in (k, counts[k])],
                      dtype=np.float64)
