"""End-to-end system behaviour: the full Cobra pipeline."""

from repro.core import CostCatalog, Interpreter, optimize
from repro.programs import make_orders_customer_db, make_p0
from repro.relational.database import ClientEnv, SLOW_REMOTE


def test_full_cobra_pipeline_p0():
    """program → region DAG → rules → cost search → codegen → execution."""
    db = make_orders_customer_db(500, 200)
    p0 = make_p0()
    res = optimize(p0, db, CostCatalog(SLOW_REMOTE))
    assert res.opt_time_s < 1.0
    assert res.memo_stats["and_nodes"] > 5
    env0, env1 = ClientEnv(db, SLOW_REMOTE), ClientEnv(db, SLOW_REMOTE)
    o0 = Interpreter(env0, "fast").run(p0)
    o1 = Interpreter(env1, "fast").run(res.program)
    assert o0["result"] == o1["result"]
    assert env1.clock < env0.clock
