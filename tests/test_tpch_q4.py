"""TPC-H Q4 ("Order Priority Checking") as an ORM report loop, at a small
scale on the CPU: its correlated EXISTS, written three ways an application
writes it, lifts to one existential fold, is served as one semi-join query,
and answers as a plain numpy Q4 does on the same seeded data.

The data follows dbgen's rules for the columns Q4 reads (sparse order
keys, 1-7 lineitems an order, ship/commit/receipt dates from the order
date, five priorities).
"""

import numpy as np
import pytest

from repro.api import CobraSession
from repro.api.builder import col, param, q
from repro.api.lift import lift_source
from repro.compiled.exec import SplicingInterpreter
from repro.compiled.lower import lower_program
from repro.core import CostCatalog, Interpreter
from repro.core.fir import FExistsE, fir_contains, loop_to_fir
from repro.relational import DatabaseServer, Field, Schema, Table
from repro.relational.database import SLOW_REMOTE, ClientEnv
from repro.runtime import ServingRuntime

N_ORDERS = 2000
N_DAYS = 2406                    # 1992-01-01 .. 1998-12-31 - 151 days
SEED = 2**31 + 16
# three-month windows [lo, hi), days since 1992-01-01
WINDOWS = [(366 + 31 * k, 366 + 31 * k + 91) for k in range(0, 48, 5)]

LOOP_HEAD = '''
def Q4(lo=0, hi=0):
    counts = {}
    for o in q("orders").where(col("o_orderdate").ge(param("lo"))
                               .and_(col("o_orderdate").lt(param("hi")))
                               ).bind(lo=lo, hi=hi):
'''
LINES = ('q("lineitem").where(col("l_orderkey").eq(param("ok")))'
         '.bind(ok=o.o_orderkey)')
COUNT = ("counts[o.o_orderpriority] = "
         "counts.get(o.o_orderpriority, 0) + 1")
FORMS = {
    "any": LOOP_HEAD + f'''\
        if any(l.l_commitdate < l.l_receiptdate for l in {LINES}):
            {COUNT}
    return counts
''',
    "flag_break": LOOP_HEAD + f'''\
        late = 0
        for l in {LINES}:
            if l.l_commitdate < l.l_receiptdate:
                late = 1
                break
        if late == 1:
            {COUNT}
    return counts
''',
    "flag": LOOP_HEAD + f'''\
        late = 0
        for l in {LINES}:
            if l.l_commitdate < l.l_receiptdate:
                late = 1
        if late == 1:
            {COUNT}
    return counts
''',
}
ENV = {"q": q, "col": col, "param": param}


def generate(n_orders: int, rng: np.random.Generator) -> dict:
    i = np.arange(n_orders)
    o_orderkey = (i // 8) * 32 + i % 8 + 1
    o_orderdate = rng.integers(0, N_DAYS, n_orders)
    o_orderpriority = rng.integers(0, 5, n_orders)
    n_per = rng.integers(1, 8, n_orders)
    odate = np.repeat(o_orderdate, n_per)
    ship = odate + rng.integers(1, 122, odate.size)
    return {
        "orders": {"o_orderkey": o_orderkey, "o_orderdate": o_orderdate,
                   "o_orderpriority": o_orderpriority},
        "lineitem": {"l_orderkey": np.repeat(o_orderkey, n_per),
                     "l_shipdate": ship,
                     "l_commitdate": odate + rng.integers(30, 91, odate.size),
                     "l_receiptdate": ship + rng.integers(1, 31, odate.size)},
    }


def reference(data: dict, lo: int, hi: int) -> dict:
    """Q4 by plain numpy: a mask, ``np.isin``, a bincount."""
    o, li = data["orders"], data["lineitem"]
    late = li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]
    m = (o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi) \
        & np.isin(o["o_orderkey"], late)
    counts = np.bincount(o["o_orderpriority"][m], minlength=5)
    return {int(p): int(c) for p, c in enumerate(counts) if c}


@pytest.fixture(scope="module")
def data():
    return generate(N_ORDERS, np.random.default_rng(SEED))


def _db(data) -> DatabaseServer:
    def table(name, cols):
        schema = Schema.of(*(Field(c, "int32", 4) for c in cols))
        return Table.from_columns(name, schema, **cols)
    return DatabaseServer({name: table(name, cols)
                           for name, cols in data.items()})


def _program(form: str):
    return lift_source(FORMS[form], env=ENV)


def test_the_three_forms_reach_one_existential_fold():
    folds = {form: loop_to_fir(_program(form).body.parts[-1])[0]
             for form in FORMS}
    assert len({f.key() for f in folds.values()}) == 1
    assert fir_contains(folds["any"], lambda e: isinstance(e, FExistsE))


@pytest.mark.parametrize("tier", ["fast", "compiled"])
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_served_answers_match_the_reference(data, form, batch, tier):
    session = CobraSession(_db(data), CostCatalog(SLOW_REMOTE))
    rt = ServingRuntime(session, batch_size=batch,
                        compile_hot_plans=1 if tier == "compiled" else None)
    exe = rt.register(_program(form))
    # the whole loop is one grouped query over a semi-join
    assert {"SJ", "T5g"} <= set(exe.result.rules_fired)
    responses = rt.serve([("Q4", {"lo": lo, "hi": hi}) for lo, hi in WINDOWS])
    for (lo, hi), r in zip(WINDOWS, responses):
        assert r.outputs["counts"] == reference(data, lo, hi)
        assert r.n_round_trips <= 1


@pytest.mark.parametrize("tier", ["exact", "fast", "compiled"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_loop_as_written_matches_the_reference(data, form, tier):
    """The unrewritten program, one existence check (a query) per order,
    on each execution tier: the flag loops' inner loops are columnar on
    the fast and compiled tiers."""
    db = _db(data)
    program = _program(form)
    lo, hi = WINDOWS[3]
    env = ClientEnv(db, SLOW_REMOTE)
    if tier == "compiled":
        lowered = lower_program(program)
        out = SplicingInterpreter(env, lowered).run(
            lowered.program, {"lo": lo, "hi": hi})
    else:
        out = Interpreter(env, tier).run(program, {"lo": lo, "hi": hi})
    assert out["counts"] == reference(data, lo, hi)
    assert env.n_round_trips > len(out["counts"])      # one check an order


@pytest.mark.parametrize("keys", ["int", "int_ordered", "negative", "float"])
def test_semijoin_matches_numpy(keys):
    """Each way the semi-join runs: a direct-address table (integer keys
    from 0, built and probed in key order where the keys are ascending)
    and a sort and search (any other keys)."""
    from repro.relational.algebra import Cmp, Col, Lit, Scan, Select, SemiJoin
    rng = np.random.default_rng(SEED)
    lk, rk = rng.integers(0, 50, 300), rng.integers(0, 50, 400)
    if keys == "int_ordered":
        lk, rk = np.sort(lk), np.sort(rk)
    if keys == "negative":
        lk, rk = lk - 25, rk - 25
    dtype = "float32" if keys == "float" else "int32"
    if keys == "float":
        lk, rk = lk + 0.5, rk + 0.5
    lv, rv = rng.integers(0, 10, 300), rng.integers(0, 10, 400)

    def table(name, k, v):
        schema = Schema.of(Field("k", dtype, 4), Field("v", "int32", 4))
        return Table.from_columns(name, schema, k=k, v=v)
    db = DatabaseServer({"l": table("l", lk, lv), "r": table("r", rk, rv)})
    semi = SemiJoin(Select(Cmp(">", Col("v"), Lit(2)), Scan("l")),
                    Select(Cmp("<", Col("v"), Lit(5)), Scan("r")), "k", "k")
    got = semi.execute(db)
    want = (lv > 2) & np.isin(lk, rk[rv < 5])
    assert np.asarray(got.column("v")).tolist() == lv[want].tolist()
    assert np.asarray(got.column("k")).tolist() == \
        lk[want].astype(dtype).tolist()
