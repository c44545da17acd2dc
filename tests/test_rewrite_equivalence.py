"""The rewriter preserves semantics: every paper program, rewritten by the
memo search, computes what the unrewritten program computes under the
exact interpreter.

  * in every network and serving context the optimizer costs for, the
    rewritten program's outputs (and the tables it writes) equal the exact
    reference on a fresh database from the same constructor; one-shot
    plans are also never slower on the simulated clock (the paper's
    "never worse");
  * leaving any one explore rule out of the search still yields a correct
    program, and never a cheaper estimate than the full rule set finds.

Storage is fp32 while the exact reference folds in Python floats, so
floats compare within rtol 1e-4 / atol 1e-4 (``test_properties``'s
tolerance); integers compare exactly.
"""

import functools

import numpy as np
import pytest

from repro.api import CobraSession, OptimizerConfig
from repro.core import CostCatalog, ExecutionContext, Interpreter, ONE_SHOT
from repro.relational.database import FAST_LOCAL, SLOW_REMOTE, ClientEnv

from paper_programs import PROGRAMS

NETWORKS = {"SLOW_REMOTE": SLOW_REMOTE, "FAST_LOCAL": FAST_LOCAL}
CONTEXTS = {"one-shot": ONE_SHOT,
            "serving-8": ExecutionContext.serving(8),
            "serving-64": ExecutionContext.serving(64)}
EXPLORE_RULES = ("T1", "T2", "T2c", "N2", "N2c", "T3", "T4", "T4j", "T5",
                 "T5g", "SJ", "N1", "N1a")


def assert_close(got, want, where):
    if isinstance(want, (list, tuple)):
        assert len(got) == len(want), where
        for i, (g, w) in enumerate(zip(got, want)):
            assert_close(g, w, f"{where}[{i}]")
    elif isinstance(want, dict):
        assert set(got) == set(want), where
        for k in want:
            assert_close(got[k], want[k], f"{where}[{k!r}]")
    elif isinstance(want, (bool, int, np.integer)):
        assert got == want, where
    else:
        assert np.isclose(float(got), float(want), rtol=1e-4, atol=1e-4), \
            (where, got, want)


def assert_tables_close(got_db, want_db):
    assert set(got_db.tables) == set(want_db.tables)
    for name, want in want_db.tables.items():
        got = got_db.table(name)
        assert got.nrows == want.nrows, name
        for col in want.schema.names:
            g = np.asarray(got.column(col))
            w = np.asarray(want.column(col))
            if np.issubdtype(w.dtype, np.floating):
                assert np.allclose(g, w, rtol=1e-4, atol=1e-4), (name, col)
            else:
                assert np.array_equal(g, w), (name, col)


@functools.lru_cache(maxsize=None)
def exact_reference(name, network_name):
    """Outputs, simulated clocks and final database of the unrewritten
    program under the exact interpreter, one run per parameter set."""
    make, mkdb, param_sets = PROGRAMS[name]
    db = mkdb()
    outs, clocks = [], []
    for params in param_sets:
        env = ClientEnv(db, NETWORKS[network_name])
        outs.append(Interpreter(env, "exact").run(make(), params or None))
        clocks.append(env.clock)
    return outs, clocks, db


def run_rewritten(name, network_name, context=ONE_SHOT, config=None):
    make, mkdb, param_sets = PROGRAMS[name]
    session = CobraSession(mkdb(), CostCatalog(NETWORKS[network_name]),
                           config=config)
    exe = session.compile(make(), context=context)
    return exe, [exe.run(**params) for params in param_sets], session.db


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("network", sorted(NETWORKS))
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_rewrite_equals_unrewritten(program, network, context):
    want_outs, want_clocks, want_db = exact_reference(program, network)
    _, runs, db = run_rewritten(program, network, CONTEXTS[context])
    for i, (run, want) in enumerate(zip(runs, want_outs)):
        assert_close(run.outputs, want, f"{program} run {i}")
    assert_tables_close(db, want_db)
    # "never worse" holds where the search knows what a run does: a plan
    # costed for batches pays for its amortised fetches in one run, and a
    # loop over a collection input (W_E's worklist) is costed at the
    # catalog's default of 1,000 keys, not the table's 0-3
    if CONTEXTS[context] is ONE_SHOT and not any(
            isinstance(default, (list, tuple))
            for _, default in PROGRAMS[program][0]().inputs):
        for run, clock in zip(runs, want_clocks):
            assert run.simulated_s <= 1.05 * clock, (program, clock)


@functools.lru_cache(maxsize=None)
def full_rule_set_costs():
    return {name: run_rewritten(name, "SLOW_REMOTE")[0].result.est_cost
            for name in PROGRAMS}


@pytest.mark.parametrize("rule", EXPLORE_RULES)
def test_leaving_out_one_rule_preserves_semantics_and_cost(rule):
    full = full_rule_set_costs()
    config = OptimizerConfig(exclude_rules=(rule,))
    assert rule in OptimizerConfig().rule_names()
    assert rule not in config.rule_names()
    for name in sorted(PROGRAMS):
        want_outs, _, want_db = exact_reference(name, "SLOW_REMOTE")
        exe, runs, db = run_rewritten(name, "SLOW_REMOTE", config=config)
        for i, (run, want) in enumerate(zip(runs, want_outs)):
            assert_close(run.outputs, want, f"{name} without {rule}, run {i}")
        assert_tables_close(db, want_db)
        assert exe.result.est_cost >= full[name], (name, rule)
