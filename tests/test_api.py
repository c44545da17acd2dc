"""The unified CobraSession API: tracing frontend, config, compile/run.

Acceptance from the redesign issue:
  * the ``ProgramBuilder`` trace produces IR byte-identical to hand-built
    Region trees;
  * ``CobraSession.compile()`` + ``Executable.run()`` reproduce the paper's
    P0 → P1/P2 rewrites end-to-end — same chosen plans and simulated costs
    as the legacy ``optimize()`` free function;
  * the session fronts the distributed TPU planner with the same
    ``PlanReport`` result vocabulary.
"""

import numpy as np
import pytest

from repro.api import (CobraSession, Executable, ExecutionResult,
                       OptimizerConfig, PlanReport, ProgramBuilder, col,
                       param, q)
from repro.core import CostCatalog, optimize
from repro.core.regions import (Assign, BasicBlock, CollectionAdd, CondRegion,
                                IBin, ICall, IConst, IEmptyList, IField,
                                ILoadAll, INav, IVar, LoopRegion, Program,
                                UpdateRow, seq)
from repro.programs import (make_m0, make_orders_customer_db, make_p0,
                            make_sales_db, make_wilos_db, make_wilos_e)
from repro.relational.database import FAST_LOCAL, SLOW_REMOTE


# --------------------------------------------------------------------------
# ProgramBuilder: trace == hand-built IR
# --------------------------------------------------------------------------

def hand_built_p0() -> Program:
    """Fig. 3a exactly as the pre-API code assembled it."""
    body = seq(
        Assign("cust", INav(IVar("o"), "o_customer_sk", "customer",
                            "c_customer_sk")),
        Assign("val", ICall("myFunc", (IField(IVar("o"), "o_id"),
                                       IField(IVar("cust"), "c_birth_year")))),
        CollectionAdd("result", IVar("val")),
    )
    return Program(
        "P0",
        seq(Assign("result", IEmptyList()),
            LoopRegion("o", ILoadAll("orders"), body, label="L3-7")),
        outputs=("result",),
    )


def hand_built_wilos_a() -> Program:
    inner = LoopRegion(
        "y", ILoadAll("tasks"),
        CondRegion(IBin("==", IField(IVar("y"), "t_role_id"),
                        IField(IVar("x"), "r_id")),
                   BasicBlock(Assign("cnt", IBin("+", IVar("cnt"), IConst(1))))))
    outer_body = seq(
        Assign("cnt", IConst(0)),
        inner,
        UpdateRow("roles", "r_rank", IVar("cnt"), "r_id",
                  IField(IVar("x"), "r_id")),
    )
    return Program("W_A", seq(LoopRegion("x", ILoadAll("roles"), outer_body)),
                   outputs=())


class TestProgramBuilder:
    def test_p0_trace_matches_hand_built(self):
        assert make_p0().key() == hand_built_p0().key()

    def test_wilos_a_trace_matches_hand_built(self):
        from repro.programs import make_wilos_a
        assert make_wilos_a().key() == hand_built_wilos_a().key()

    def test_single_statement_scopes_stay_unwrapped(self):
        """A one-region loop body / cond branch is NOT seq-wrapped (matches
        how the hand-built programs nested regions)."""
        b = ProgramBuilder("t")
        r = b.let("r", b.empty_list())
        with b.loop(b.load_all("tasks"), var="t") as t:
            with b.when(t.t_state == 1):
                b.add(r, t.t_hours)
        p = b.build(outputs=(r,))
        loop = p.body.parts[1]
        assert isinstance(loop, LoopRegion)
        assert isinstance(loop.body, CondRegion)              # not SeqRegion
        assert isinstance(loop.body.then_r, BasicBlock)       # not SeqRegion

    def test_operator_tracing(self):
        b = ProgramBuilder("t")
        x = b.var("x")
        e = (x + 1) * 2 == x.f
        assert e.ir.key() == IBin("==", IBin("*", IBin("+", IVar("x"),
                                                       IConst(1)), IConst(2)),
                                  IField(IVar("x"), "f")).key()

    def test_expr_has_no_truth_value(self):
        b = ProgramBuilder("t")
        with pytest.raises(TypeError, match="when"):
            bool(b.var("x") == 1)

    def test_unclosed_scope_rejected(self):
        b = ProgramBuilder("t")
        cm = b.loop(b.load_all("tasks"), var="t")
        cm.__enter__()
        with pytest.raises(RuntimeError, match="unclosed"):
            b.build()

    def test_otherwise_requires_when(self):
        b = ProgramBuilder("t")
        with pytest.raises(RuntimeError, match="otherwise"):
            with b.otherwise():
                pass

    def test_otherwise_fills_else_branch(self):
        b = ProgramBuilder("t")
        n = b.let("n", 0)
        with b.loop(b.load_all("tasks"), var="t") as t:
            with b.when(t.t_state == 1):
                b.let("n", n + 1)
            with b.otherwise():
                b.let("n", n + 2)
        p = b.build(outputs=(n,))
        cond = p.body.parts[1].body
        assert isinstance(cond, CondRegion) and cond.else_r is not None

    def test_query_handles_compose(self):
        h = q("tasks").where(col("t_role_id").eq(param("rid"))) \
                      .select("t_hours").order_by("t_hours").limit(5)
        assert "WHERE" in h.sql() and "LIMIT 5" in h.sql()
        bound = h.bind(rid=IVar("w"))
        assert bound.bindings == (("rid", IVar("w")),)


# --------------------------------------------------------------------------
# OptimizerConfig
# --------------------------------------------------------------------------

class TestOptimizerConfig:
    def test_preset_paper_excludes_t3(self):
        names = OptimizerConfig.preset("paper-exp1-3").rule_names()
        assert "T3" not in names and "T1" in names

    def test_preset_full_has_every_rule(self):
        from repro.core.rules import default_rules
        assert set(OptimizerConfig.preset("full").rule_names()) == \
            {r.name for r in default_rules()}

    def test_unknown_preset_and_rule_rejected(self):
        with pytest.raises(ValueError, match="preset"):
            OptimizerConfig.preset("nope")
        with pytest.raises(ValueError, match="unknown rule"):
            OptimizerConfig(rules=("T1", "bogus")).resolve_rules()

    def test_invalid_choice_rejected(self):
        with pytest.raises(ValueError, match="choice"):
            OptimizerConfig(choice="vibes")

    def test_preset_overrides(self):
        cfg = OptimizerConfig.preset("paper-exp1-3", topk=2)
        assert cfg.topk == 2 and cfg.exclude_rules == ("T3",)


# --------------------------------------------------------------------------
# Session compile/run ≡ legacy optimize()
# --------------------------------------------------------------------------

def legacy_paper_rules():
    from repro.core.rules import default_rules
    return [r for r in default_rules() if r.name != "T3"]


class TestSessionEndToEnd:
    @pytest.mark.parametrize("n_orders,n_cust,expect", [
        (100, 5000, "JOIN"),        # Experiment 1: P0 -> P1
        (4000, 500, "prefetch"),    # Experiment 2: P0 -> P2
    ])
    def test_p0_rewrites_match_optimize(self, n_orders, n_cust, expect):
        db = make_orders_customer_db(n_orders, n_cust)
        legacy = optimize(make_p0(), db, CostCatalog(SLOW_REMOTE),
                          rules=legacy_paper_rules())
        session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                               config=OptimizerConfig.preset("paper-exp1-3"))
        exe = session.compile(make_p0())
        assert expect in repr(exe.program.body)
        # same chosen plan and simulated cost as the legacy entry point —
        # codegen names are alpha-normalized per run, so two searches of the
        # same program emit byte-identical IR
        assert exe.program.body.key() == legacy.program.body.key()
        assert exe.est_cost_s == pytest.approx(legacy.est_cost)

    def test_run_is_semantics_preserving_and_faster(self):
        db = make_orders_customer_db(500, 100)
        session = CobraSession(db, CostCatalog(SLOW_REMOTE))
        p0 = make_p0()
        base = session.execute(p0)
        exe = session.compile(p0)
        out = exe.run()
        a = np.sort(np.asarray(base["result"], dtype=np.float64))
        c = np.sort(np.asarray(out["result"], dtype=np.float64))
        assert np.allclose(a, c, rtol=1e-4)
        assert out.simulated_s <= base.simulated_s
        assert isinstance(out, ExecutionResult) and out.n_queries >= 1

    def test_execute_many_with_params(self):
        db = make_wilos_db(500, ratio=10)
        session = CobraSession(db, CostCatalog(FAST_LOCAL))
        exe = session.compile(make_wilos_e())
        r1 = exe.run(worklist=[1, 3])
        r2 = exe.run(worklist=[2])
        r3 = exe.run(worklist=[1, 3])
        assert exe.n_runs == 3 and session.executions == 3
        assert sorted(r1["result"]) == sorted(r3["result"])
        assert len(r2["result"]) != len(r1["result"])

    def test_m0_single_query_via_session(self):
        db = make_sales_db(2000)
        session = CobraSession(db, CostCatalog(SLOW_REMOTE))
        out = session.compile(make_m0()).run()
        assert out.n_queries == 1
        base = session.execute(make_m0())
        assert out["total"] == pytest.approx(base["total"], rel=1e-4)

    def test_heuristic_config_refuses_prefetch(self):
        from repro.programs import make_wilos_a
        db = make_wilos_db(1000)
        session = CobraSession(db, CostCatalog(FAST_LOCAL))
        exe_c = session.compile(make_wilos_a())
        exe_h = session.compile(make_wilos_a(),
                                config=OptimizerConfig.preset("heuristic"))
        assert "prefetch" in repr(exe_c.program.body)
        assert "prefetch" not in repr(exe_h.program.body)

    def test_report_vocabulary(self):
        db = make_orders_customer_db(100, 100)
        session = CobraSession(db, CostCatalog(SLOW_REMOTE))
        rep = session.compile(make_p0()).report
        assert isinstance(rep, PlanReport)
        assert rep.alternatives >= 1 and rep.est_cost_s > 0
        assert "P0" in rep.describe()

    def test_codegen_alpha_normalized_across_sessions(self):
        """Two independent sessions compiling the same program emit
        byte-identical rewritten IR (content-stable codegen names) — the
        property the cross-session plan store's dedupe rests on."""
        exes = []
        for _ in range(2):
            db = make_orders_customer_db(4000, 500)
            session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                                   config=OptimizerConfig.preset("paper-exp1-3"))
            exes.append(session.compile(make_p0()))
        assert exes[0].program.body.key() == exes[1].program.body.key()


# --------------------------------------------------------------------------
# session.trace() decorator
# --------------------------------------------------------------------------

class TestTraceDecorator:
    def _session(self):
        return CobraSession(make_wilos_db(300, ratio=10),
                            CostCatalog(FAST_LOCAL))

    def test_trace_turns_function_into_executable(self):
        session = self._session()

        @session.trace
        def task_hours(b, worklist=()):
            out = b.let("out", b.empty_list())
            with b.loop(worklist, var="wid") as wid:
                per_key = q("tasks").where(col("t_role_id").eq(param("rid"))) \
                                    .bind(rid=wid)
                with b.loop(per_key, var="y") as y:
                    b.add(out, y.t_hours)
            return out

        assert isinstance(task_hours, Executable)
        # the traced program matches the hand-built equivalent (make_wilos_e)
        src = task_hours.source
        assert src.inputs == (("worklist", ()),)
        r1 = task_hours.run(worklist=[1, 3])
        r2 = session.compile(make_wilos_e()).run(worklist=[1, 3])
        assert sorted(r1["out"]) == sorted(r2["result"])

    def test_trace_with_name_and_multiple_outputs(self):
        session = self._session()

        @session.trace(name="two_aggs")
        def f(b):
            n = b.let("n", 0)
            hours = b.let("hours", 0.0)
            with b.loop(b.load_all("tasks"), var="t") as t:
                b.let("n", n + 1)
                b.let("hours", hours + t.t_hours)
            return n, hours

        assert f.source.name == "two_aggs"
        out = f.run()
        assert out["n"] == session.db.table("tasks").nrows
        assert out["hours"] > 0

    def test_trace_hits_plan_cache(self):
        session = self._session()

        def body(b):
            total = b.let("total", 0.0)
            with b.loop(b.load_all("tasks"), var="t") as t:
                b.let("total", total + t.t_hours)
            return total

        exe1 = session.trace(body, name="agg")
        exe2 = session.trace(body, name="agg")
        assert not exe1.from_cache and exe2.from_cache


# --------------------------------------------------------------------------
# Back-compat shim
# --------------------------------------------------------------------------

def test_optimize_shim_unchanged_signature():
    """repro.core.optimize keeps its exact legacy behaviour (tier-1 tests
    elsewhere exercise it heavily); it now routes through a session."""
    db = make_orders_customer_db(200, 400)
    res = optimize(make_p0(), db, CostCatalog(SLOW_REMOTE))
    assert res.est_cost > 0 and res.opt_time_s < 1.0
    assert res.program.outputs == ("result",)
