"""Deployment ``exp1-orders-1m``: the tables and program P0 of the paper's
Experiment 1, built for the program under test.

The data comes from ``exp1-orders-1m.reference.py`` (``generate``), so the
program and the reference see the same rows. Schemas and row widths, and
the source of P0, are copied from ``repro.programs``
(``make_orders_customer_db``, ``make_p0``).
"""

from __future__ import annotations

from repro.api.lift import lift_program, load_all
from repro.core.regions import get_function
from repro.relational.algebra import register_scalar_func
from repro.relational.database import DatabaseServer
from repro.relational.table import Field, Schema, Table

myFunc = get_function("myFunc")
register_scalar_func("myFunc", myFunc)

ORDERS_CUSTOMER_REL = ("orders", "o_customer_sk",
                       "customer", "c_customer_sk", "customer")


def build_db(columns: dict) -> DatabaseServer:
    """TPC-DS-sized rows: customer 132 B, orders (store_sales-like) 100 B
    on the wire; the columns go to JAX's default device."""
    c, o = columns["customer"], columns["orders"]
    customer = Table.from_columns(
        "customer",
        Schema.of(Field("c_customer_sk", "int64", 8),
                  Field("c_birth_year", "int32", 4),
                  Field("c_credit", "float32", 4),
                  Field("c_payload", "int32", 116)),
        **c)
    orders = Table.from_columns(
        "orders",
        Schema.of(Field("o_id", "int64", 8),
                  Field("o_customer_sk", "int64", 8),
                  Field("o_amt", "float32", 4),
                  Field("o_payload", "int32", 80)),
        **o)
    return DatabaseServer({"customer": customer, "orders": orders})


def programs() -> list:
    """Hibernate ORM program P0: per-order navigation, N+1 selects."""
    def P0():
        result = []
        for o in load_all("orders"):
            cust = o.customer  # lazy relationship -> point query
            val = myFunc(o.o_id, cust.c_birth_year)
            result.append(val)
        return result

    return [lift_program(P0, relations=[ORDERS_CUSTOMER_REL])]
