"""Data and plain reference of ``exp1-orders-1m``. Imports nothing of the
program under test.

``generate`` draws the rows of ``repro.programs.make_orders_customer_db``
(same columns, ranges and order of draws) from the benchmark's seed.
``reference`` is P0 by plain numpy: each order's customer by key, then
``myFunc(a, b) = a + 2 * b``. ``control`` is the same computed in
bfloat16, the precision below the int32 and float32 the deployment stores.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def generate(sizes: dict, rng: np.random.Generator) -> dict:
    n_orders, n_customers = int(sizes["n_orders"]), int(sizes["n_customers"])
    customer = {
        "c_customer_sk": np.arange(n_customers, dtype=np.int64),
        "c_birth_year": rng.integers(1930, 2005, n_customers),
        "c_credit": rng.uniform(0, 1e4, n_customers).astype(np.float32),
        "c_payload": rng.integers(0, 1 << 20, n_customers),
    }
    orders = {
        "o_id": np.arange(n_orders, dtype=np.int64),
        "o_customer_sk": rng.integers(0, n_customers, n_orders),
        "o_amt": rng.uniform(1, 500, n_orders).astype(np.float32),
        "o_payload": rng.integers(0, 1 << 20, n_orders),
    }
    return {"customer": customer, "orders": orders}


def _years(columns: dict) -> np.ndarray:
    c, o = columns["customer"], columns["orders"]
    year_by_key = np.zeros(int(c["c_customer_sk"].max()) + 1, np.int64)
    year_by_key[c["c_customer_sk"]] = c["c_birth_year"]
    return year_by_key[o["o_customer_sk"]]


def reference(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "P0":
        raise KeyError(program)
    return columns["orders"]["o_id"].astype(np.int64) + 2 * _years(columns)


def control(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "P0":
        raise KeyError(program)
    bf16 = ml_dtypes.bfloat16
    o_id = columns["orders"]["o_id"].astype(bf16)
    twice = (2 * _years(columns)).astype(bf16)
    return (o_id + twice).astype(np.float64)


def answer(outputs: dict) -> np.ndarray:
    """The served answer as a vector comparable with ``reference``."""
    return np.asarray(outputs["result"])
