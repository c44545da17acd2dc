"""Deployment ``tpch-q4-sf1``: TPC-H's ``orders`` and ``lineitem`` at scale
factor 1, and Q4 ("Order Priority Checking", spec 2.4.4) as an ORM
report loop, built for the program under test.

The data comes from ``tpch-q4-sf1.reference.py`` (``generate``), so the
program and the reference see the same rows. Row widths on the wire are
the spec's column types (1.4): identifiers and integers 4 B, decimals
8 B, dates 4 B, ``char(N)``/``varchar(N)`` N B; every column is stored as
int32.
"""

from __future__ import annotations

from repro.api.builder import col, param, q
from repro.api.lift import lift_program
from repro.core.regions import register_function
from repro.relational.database import DatabaseServer
from repro.relational.table import Field, Schema, Table

# the day a request's start offset counts from: 1993-01-01, in days since
# 1970-01-01 (the dates' storage)
FIRST_START_DAY = 8401

ORDERS = (("o_orderkey", 4), ("o_custkey", 4), ("o_orderstatus", 1),
          ("o_totalprice", 8), ("o_orderdate", 4), ("o_orderpriority", 15),
          ("o_clerk", 15), ("o_shippriority", 4), ("o_comment", 79))
LINEITEM = (("l_orderkey", 4), ("l_partkey", 4), ("l_suppkey", 4),
            ("l_linenumber", 4), ("l_quantity", 8), ("l_extendedprice", 8),
            ("l_discount", 8), ("l_tax", 8), ("l_returnflag", 1),
            ("l_linestatus", 1), ("l_shipdate", 4), ("l_commitdate", 4),
            ("l_receiptdate", 4), ("l_shipinstruct", 25), ("l_shipmode", 10),
            ("l_comment", 44))


def _days_from_civil(y, m, d):
    """Days since 1970-01-01 of a proleptic Gregorian date (integer
    arithmetic only, so it runs on Python ints and arrays alike)."""
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    doy = (153 * ((m + 9) % 12) + 2) // 5 + d - 1
    return era * 146097 + yoe * 365 + yoe // 4 - yoe // 100 + doy - 719468


def _civil_from_days(z):
    z = z + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + 3 - 12 * (mp >= 10)
    return yoe + era * 400 + (m <= 2), m, d


def day_of(offset):
    """The start day of a request: ``offset`` days after 1993-01-01."""
    return offset + FIRST_START_DAY


def add_months(day, months):
    """``day + interval 'months' month``: the same day of the month
    ``months`` later, or that month's last day where it has fewer."""
    y, m, d = _civil_from_days(day)
    m0 = m - 1 + months
    y, m = y + m0 // 12, m0 % 12 + 1
    month_days = _days_from_civil(y + m // 12, m % 12 + 1, 1) \
        - _days_from_civil(y, m, 1)
    return _days_from_civil(y, m, (d + month_days - abs(d - month_days)) // 2)


register_function("day_of", day_of)
register_function("add_months", add_months)


def _schema(cols) -> Schema:
    return Schema.of(*(Field(name, "int32", width) for name, width in cols))


def build_db(columns: dict) -> DatabaseServer:
    """``orders`` and ``lineitem``, every TPC-H column, on JAX's default
    device."""
    return DatabaseServer({
        "orders": Table.from_columns("orders", _schema(ORDERS),
                                     **columns["orders"]),
        "lineitem": Table.from_columns("lineitem", _schema(LINEITEM),
                                       **columns["lineitem"])})


def programs() -> list:
    """Q4 as a reporting application writes it: a loop over the orders of
    the three months, an existence check over each order's lineitems, and
    a count by priority."""
    def Q4(start=()):
        lo = day_of(start[0])
        hi = add_months(lo, 3)
        counts = {}
        for o in q("orders").where(col("o_orderdate").ge(param("lo"))
                                   .and_(col("o_orderdate").lt(param("hi")))
                                   ).bind(lo=lo, hi=hi):
            if any(l.l_commitdate < l.l_receiptdate
                   for l in q("lineitem").where(col("l_orderkey")
                                                .eq(param("ok")))
                   .bind(ok=o.o_orderkey)):
                counts[o.o_orderpriority] = \
                    counts.get(o.o_orderpriority, 0) + 1
        return counts

    return [lift_program(Q4)]
