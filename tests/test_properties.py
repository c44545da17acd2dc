"""Property-based tests (hypothesis) for system invariants.

  * fast (vectorized) interpreter ≡ exact interpreter: same output state AND
    same simulated clock, on randomized programs/data;
  * F-IR conversion ≡ direct loop execution;
  * every rule-produced alternative is semantics-preserving (the memo's
  	alternatives all compute the same transition);
  * join index machinery ≡ brute force.
"""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (pip install hypothesis) — see pyproject.toml")
from hypothesis import given, settings, strategies as st

from repro.core import CostCatalog, Interpreter, optimize
from repro.core.fir import eval_fir, loop_to_fir
from repro.core.regions import (Assign, CollectionAdd, CondRegion, IBin,
                                IConst, IEmptyList, IEmptyMap, IField,
                                ILoadAll, IMapGet, IVar, LoopRegion, MapPut,
                                Program, seq)
from repro.relational import (DatabaseServer, Field, Schema, Table,
                              equi_join_indices)
from repro.relational.database import ClientEnv, FAST_LOCAL, SLOW_REMOTE


# --------------------------------------------------------------------------
# data strategies
# --------------------------------------------------------------------------

@st.composite
def small_db(draw):
    n = draw(st.integers(1, 40))
    nk = draw(st.integers(1, 8))
    seed = draw(st.integers(0, 2**16))
    rng = np.random.default_rng(seed)
    items = Table.from_columns(
        "items",
        Schema.of(Field("i_id", "int64", 8), Field("i_k", "int64", 8),
                  Field("i_v", "float32", 4), Field("i_w", "int32", 4)),
        i_id=np.arange(n), i_k=rng.integers(0, nk, n),
        i_v=rng.uniform(0, 10, n).astype(np.float32),
        i_w=rng.integers(0, 100, n))
    keys = Table.from_columns(
        "keys",
        Schema.of(Field("k_id", "int64", 8), Field("k_r", "int32", 4)),
        k_id=np.arange(nk), k_r=rng.integers(0, 5, nk))
    return DatabaseServer({"items": items, "keys": keys})


@st.composite
def accumulating_loop(draw):
    """A random cursor loop with 1–3 accumulators (incl. dependent/guarded)."""
    stmts = []
    outputs = []
    use_guard = draw(st.booleans())
    body = []
    if draw(st.booleans()):
        body.append(Assign("s", IBin("+", IVar("s"), IField(IVar("t"), "i_v"))))
        stmts.append(Assign("s", IConst(0.0)))
        outputs.append("s")
    if draw(st.booleans()):
        body.append(Assign("mx", IBin("max", IVar("mx"),
                                      IField(IVar("t"), "i_w"))))
        stmts.append(Assign("mx", IConst(0)))
        outputs.append("mx")
    body.append(CollectionAdd("out", IBin("*", IField(IVar("t"), "i_v"),
                                          IConst(2.0))))
    stmts.append(Assign("out", IEmptyList()))
    outputs.append("out")
    if draw(st.booleans()) and "s" in outputs:
        body.append(MapPut("m", IField(IVar("t"), "i_k"), IVar("s")))
        stmts.append(Assign("m", IEmptyMap()))
        outputs.append("m")
    inner = seq(*body)
    if use_guard:
        inner = CondRegion(IBin("<", IField(IVar("t"), "i_w"), IConst(50)), inner)
    loop = LoopRegion("t", ILoadAll("items"), inner)
    return Program("rand", seq(*stmts, loop), tuple(outputs))


@st.composite
def flag_loop(draw):
    """A cursor loop that sets a flag (and maybe a plain temporary) in its
    body, both read after the loop: the flag under a guard, to a constant
    or to a value of the row, with or without a running sum and a keyed
    accumulation beside it."""
    guard = IBin("<", IField(IVar("t"), "i_w"), IConst(draw(st.integers(0, 100))))
    value = IField(IVar("t"), "i_k") if draw(st.booleans()) else IConst(1)
    body = [CondRegion(guard, seq(Assign("flag", value)))]
    stmts = [Assign("flag", IConst(-1))]
    outputs = ["flag"]
    if draw(st.booleans()):
        body.insert(draw(st.integers(0, 1)),
                    Assign("last", IField(IVar("t"), "i_w")))
        stmts.append(Assign("last", IConst(-1)))
        outputs.append("last")
    if draw(st.booleans()):
        body.append(Assign("s", IBin("+", IVar("s"), IField(IVar("t"), "i_v"))))
        stmts.append(Assign("s", IConst(0.0)))
        outputs.append("s")
    if draw(st.booleans()):
        # keyed accumulation m[k] = m.get(k, 0) + delta, maybe under an
        # `if` of its own after the flag's
        key = IField(IVar("t"), "i_k")
        delta = draw(st.sampled_from([IConst(1), IField(IVar("t"), "i_w"),
                                      IField(IVar("t"), "i_v")]))
        put = MapPut("m", key, IBin("+", IMapGet(IVar("m"), key, IConst(0)),
                                    delta))
        if draw(st.booleans()):
            put = CondRegion(IBin(">", IField(IVar("t"), "i_w"),
                                  IConst(draw(st.integers(0, 100)))), seq(put))
        body.append(put)
        stmts.append(Assign("m", IEmptyMap()))
        outputs.append("m")
    loop = LoopRegion("t", ILoadAll("items"), seq(*body))
    # read after the loop: the flag decides what is appended
    after = CondRegion(IBin("==", IVar("flag"), IConst(-1)),
                       seq(CollectionAdd("seen", IConst(0))))
    return Program("flag", seq(*stmts, Assign("seen", IEmptyList()), loop,
                               after), tuple(outputs) + ("seen",))


def _state_close(a, b):
    for k in a:
        va, vb = a[k], b[k]
        if isinstance(va, list):
            assert len(va) == len(vb)
            assert np.allclose(np.asarray(va, np.float64),
                               np.asarray(vb, np.float64), rtol=1e-4, atol=1e-4), k
        elif isinstance(va, dict):
            assert set(va) == set(vb)
            for kk in va:
                assert abs(float(va[kk]) - float(vb[kk])) < 1e-3 * max(1, abs(float(va[kk]))), k
        elif isinstance(va, (int, float)):
            assert abs(float(va) - float(vb)) <= 1e-3 * max(1.0, abs(float(va))), k


# --------------------------------------------------------------------------
# properties
# --------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(db=small_db(), prog=accumulating_loop())
def test_fast_interpreter_equals_exact(db, prog):
    env1 = ClientEnv(db, SLOW_REMOTE)
    o1 = Interpreter(env1, "exact").run(prog)
    env2 = ClientEnv(db, SLOW_REMOTE)
    o2 = Interpreter(env2, "fast").run(prog)
    _state_close(o1, o2)
    assert abs(env1.clock - env2.clock) < 1e-9 + 1e-6 * env1.clock
    assert env1.n_queries == env2.n_queries


@pytest.mark.parametrize("tier", ["fast", "compiled"])
@settings(max_examples=40, deadline=None)
@given(db=small_db(), prog=flag_loop())
def test_variable_set_in_loop_body_equals_exact(tier, db, prog):
    """A variable assigned in a columnar loop body ends with the value of
    its last executed assignment, and a keyed accumulation with the exact
    interpreter's map, on the fast and the compiled tier."""
    from repro.compiled.exec import SplicingInterpreter
    from repro.compiled.lower import lower_program
    env1 = ClientEnv(db, SLOW_REMOTE)
    o1 = Interpreter(env1, "exact").run(prog)
    env2 = ClientEnv(db, SLOW_REMOTE)
    if tier == "fast":
        o2 = Interpreter(env2, "fast").run(prog)
    else:
        lowered = lower_program(prog)
        assert lowered.n_columnar == 1
        o2 = SplicingInterpreter(env2, lowered).run(lowered.program)
    assert o1["flag"] == o2["flag"] and o1["seen"] == o2["seen"]
    assert o1.get("m") == o2.get("m")          # sums in row order: exact
    _state_close(o1, o2)
    assert abs(env1.clock - env2.clock) < 1e-9 + 1e-6 * env1.clock


@settings(max_examples=40, deadline=None)
@given(db=small_db(), prog=accumulating_loop())
def test_fir_fold_equals_loop(db, prog):
    loop = prog.body.parts[-1]
    try:
        fold, idx = loop_to_fir(loop)
    except Exception:
        return  # not all random loops are representable; that's fine
    import copy
    env1 = ClientEnv(db, SLOW_REMOTE)
    exact = Interpreter(env1, "exact")
    state = {}
    for p in prog.body.parts[:-1]:
        exact.exec_region(p, state)
    init_state = copy.deepcopy(state)
    exact.exec_region(loop, state)
    env2 = ClientEnv(db, SLOW_REMOTE)
    vals = eval_fir(fold, env2, init_state)
    got = {v: vals[i] for v, i in idx.items()}
    _state_close({k: state[k] for k in got}, got)


@settings(max_examples=25, deadline=None)
@given(db=small_db(), prog=accumulating_loop(), slow=st.booleans())
def test_optimizer_preserves_semantics_and_cost(db, prog, slow):
    net = SLOW_REMOTE if slow else FAST_LOCAL
    env0 = ClientEnv(db, net)
    o0 = Interpreter(env0, "fast").run(prog)
    res = optimize(prog, db, CostCatalog(net))
    env1 = ClientEnv(db, net)
    o1 = Interpreter(env1, "fast").run(res.program)
    _state_close(o0, o1)
    assert env1.clock <= env0.clock * 1.2 + 1e-6


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 8), max_size=30),
       st.lists(st.integers(0, 8), max_size=30))
def test_join_indices_match_bruteforce(lk, rk):
    lk = np.asarray(lk, dtype=np.int64)
    rk = np.asarray(rk, dtype=np.int64)
    li, ri = equi_join_indices(lk, rk)
    got = sorted(zip(li.tolist(), ri.tolist()))
    want = sorted((i, j) for i in range(len(lk)) for j in range(len(rk))
                  if lk[i] == rk[j])
    assert got == want


@settings(max_examples=20, deadline=None)
@given(db=small_db())
def test_memo_alternatives_all_equivalent(db):
    """Every alternative in the expanded Region DAG computes the same state."""
    from repro.core.dag import expand
    from repro.core.rules import RuleContext, build_memo, default_rules
    from repro.core.search import Searcher, plan_to_region, hoist_prefetches
    from repro.core.cost import CostModel

    prog = Program("m", seq(
        Assign("s", IConst(0.0)),
        Assign("out", IEmptyList()),
        LoopRegion("t", ILoadAll("items"), seq(
            Assign("s", IBin("+", IVar("s"), IField(IVar("t"), "i_v"))),
            CollectionAdd("out", IField(IVar("t"), "i_w")),
        ))), ("s", "out"))
    env0 = ClientEnv(db, FAST_LOCAL)
    o0 = Interpreter(env0, "exact").run(prog)

    ctx = RuleContext(db=db)
    memo, root = build_memo(prog, ctx)
    expand(memo, default_rules(), ctx)
    cm = CostModel(db, CostCatalog(FAST_LOCAL))
    searcher = Searcher(memo, cm, ctx)
    plans = searcher.group_plans(root)
    assert plans
    for plan in plans:  # each top-K alternative must be equivalent
        region = hoist_prefetches(plan_to_region(plan))
        alt = Program("alt", region, prog.outputs)
        env1 = ClientEnv(db, FAST_LOCAL)
        o1 = Interpreter(env1, "exact").run(alt)
        _state_close(o0, o1)
