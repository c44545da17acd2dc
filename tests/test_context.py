"""ExecutionContext-aware optimizer surface: RuleSet, CostModel, context.

Issue acceptance:
  * the memo search selects a DIFFERENT winning plan for
    ``ExecutionContext(batch_size=1)`` vs ``batch_size=64`` on a paper
    program (W_E, Fig. 14 pattern E; also the while/early-exit SCAN);
  * a user-defined rule registered via the public ``RuleSet`` API fires
    and wins a plan without modifying ``core/rules.py``;
  * observed iteration counts in the context's ``StatsProfile`` (not
    ``while_iters_default``) change which alternative wins;
  * plan-cache / plan-store keys carry the context fingerprint;
  * ``OptimizerConfig.cost_model`` plugs a user CostModel subclass into
    the search.
"""

import dataclasses

import pytest

from repro.api import (CobraSession, CostModel, ExecutionContext,
                       OptimizerConfig, RuleSet, StatsProfile, add_slot_variant,
                       cobra_rule, program_sites, slot_view)
from repro.core import (CostCatalog, LoopRegion, WhileRegion, loop_site_key,
                        while_site_key)
from repro.core.cost import query_has_params
from repro.programs import (make_orders_customer_db, make_p0, make_scan,
                            make_wilos_db, make_wilos_e)
from repro.relational.algebra import Cmp, Col, Param, Scan, Select
from repro.relational.database import SLOW_REMOTE
from repro.runtime import ServingRuntime


def wilos_session(n_tasks=300, **kw):
    return CobraSession(make_wilos_db(n_tasks, ratio=10),
                        CostCatalog(SLOW_REMOTE),
                        config=OptimizerConfig.preset("paper-exp1-3"), **kw)


def find_region(program, kind):
    def walk(r):
        if isinstance(r, kind):
            return r
        for c in r.children():
            found = walk(c)
            if found is not None:
                return found
    return walk(program.body)


def scan_while_site():
    return while_site_key(find_region(make_scan(), WhileRegion).pred)


def we_loop_site():
    lp = find_region(make_wilos_e(), LoopRegion)
    return loop_site_key(lp.var, lp.source)


def plan_kind(exe_or_result):
    program = getattr(exe_or_result, "program", exe_or_result)
    body = repr(program.body)
    return "prefetch" if "prefetch" in body else \
        "join" if "JOIN" in body else "query"


# --------------------------------------------------------------------------
# Acceptance: batch size flips the winning plan
# --------------------------------------------------------------------------

class TestBatchSizeFlipsPlan:
    def test_wilos_e_flips_between_one_shot_and_batch64(self):
        """Pattern E with a short observed worklist: at batch_size=1 the
        correlated per-key σ wins (one small fetch beats pulling all of
        ``tasks``); at batch_size=64 the prefetch site — identical for every
        invocation, so fetched once per batch — amortizes to C_Q/64 and
        wins. Same program, same statistics, same rules: only the
        ExecutionContext differs."""
        stats = StatsProfile.of({we_loop_site(): 1.0})
        session = wilos_session()
        one = session.compile(make_wilos_e(),
                              context=ExecutionContext(batch_size=1,
                                                       stats=stats))
        big = session.compile(make_wilos_e(),
                              context=ExecutionContext(batch_size=64,
                                                       stats=stats))
        assert plan_kind(one) == "query"
        assert plan_kind(big) == "prefetch"
        assert one.program.body.key() != big.program.body.key()
        # both plans compute identical results
        r1 = one.run(worklist=[1, 3])
        r2 = big.run(worklist=[1, 3])
        assert r1.outputs == r2.outputs

    def test_scan_flips_inside_while_body(self):
        """SCAN's while body never hoists prefetches across the guard, so at
        batch_size=1 the T5 correlated aggregate (one round trip per
        iteration) wins; batched, the binding-free prefetch site inside the
        body is fetched once per BATCH (shared site cache) and wins."""
        session = wilos_session()
        one = session.compile(make_scan(), context=ExecutionContext())
        big = session.compile(make_scan(),
                              context=ExecutionContext(batch_size=64))
        assert plan_kind(one) == "query"
        assert plan_kind(big) == "prefetch"
        assert big.est_cost_s < one.est_cost_s

    def test_batched_cost_is_cheaper_never_pricier(self):
        session = wilos_session()
        costs = [session.compile(make_scan(),
                                 context=ExecutionContext(batch_size=b)
                                 ).est_cost_s
                 for b in (1, 8, 64)]
        assert costs[0] > costs[1] > costs[2]

    def test_p0_winner_stable_across_batch_sizes(self):
        """P0's alternatives (N+1 / join / prefetch) are all binding-free,
        so batching amortizes them equally — the winner must NOT flip."""
        db = make_orders_customer_db(100, 5000)
        session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                               config=OptimizerConfig.preset("paper-exp1-3"))
        kinds = {plan_kind(session.compile(
            make_p0(), context=ExecutionContext(batch_size=b)))
            for b in (1, 64)}
        assert len(kinds) == 1


# --------------------------------------------------------------------------
# Acceptance: observed iteration counts change the winner
# --------------------------------------------------------------------------

class TestObservedIterations:
    def test_observed_while_iters_flip_winner_at_fixed_batch(self):
        """At batch_size=2, a short-lived while (observed 1 iteration)
        keeps the per-iteration aggregate query; a long-lived one (observed
        16) makes the once-per-batch prefetch win. The catalog default
        (while_iters_default=8) never moves — only the observation does."""
        session = wilos_session()
        site = scan_while_site()
        short = session.compile(make_scan(), context=ExecutionContext(
            batch_size=2, stats=StatsProfile.of({site: 1.0})))
        long_ = session.compile(make_scan(), context=ExecutionContext(
            batch_size=2, stats=StatsProfile.of({site: 16.0})))
        assert plan_kind(short) == "query"
        assert plan_kind(long_) == "prefetch"

    def test_observed_loop_iters_scale_cost(self):
        """W_E's worklist loop has no table statistics behind it; observed
        lengths replace loop_iters_default in the estimate."""
        session = wilos_session()
        site = we_loop_site()
        est = {}
        for n in (1.0, 100.0):
            exe = session.compile(make_wilos_e(), context=ExecutionContext(
                stats=StatsProfile.of({site: n})))
            est[n] = exe.est_cost_s
        assert est[100.0] > est[1.0]

    def test_unobserved_site_uses_catalog_default(self):
        session = wilos_session()
        default = session.compile(make_scan())
        other = session.compile(make_scan(), context=ExecutionContext(
            stats=StatsProfile.of({"while:unrelated0000": 1000.0})))
        assert default.est_cost_s == other.est_cost_s
        # ...and the unrelated observation does not even change the cache
        # key: the fingerprint is restricted to the program's own sites
        assert other.from_cache


# --------------------------------------------------------------------------
# Acceptance: observed binding diversity amortizes parameterized sites
# --------------------------------------------------------------------------

class TestBindingDiversity:
    def _tasks_group(self):
        from repro.core import param_group_key
        return param_group_key(("tasks",))

    def test_observed_diversity_flips_we_plan(self):
        """THE binding-diversity flip (issue acceptance): at batch_size=64
        with a short observed worklist, the binding-free prefetch wins under
        the 0/1 rule (parameterized σ never amortizes) — but an observed
        distinct-binding fraction of 1/64 (every invocation reuses the same
        worklist key, so the site cache serves 63 of 64 fetches) amortizes
        the σ site to C_Q/64 and the query plan wins instead. Same program,
        same statistics, same batch size: only the observed diversity
        differs."""
        session = wilos_session()
        iters = {we_loop_site(): 1.0}
        base = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=64, stats=StatsProfile.of(iters)))
        amortized = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=64, stats=StatsProfile.of(
                iters, bindings={self._tasks_group(): 1.0 / 64})))
        assert plan_kind(base) == "prefetch"
        assert plan_kind(amortized) == "query"
        assert amortized.est_cost_s < base.est_cost_s
        # both compute identical results
        assert base.run(worklist=[1, 3]).outputs == \
            amortized.run(worklist=[1, 3]).outputs

    def test_high_diversity_keeps_unamortized_winner(self):
        """Fully diverse bindings (d=1.0) must price like no sharing at
        all — the conservative default."""
        session = wilos_session()
        iters = {we_loop_site(): 1.0}
        none = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=64, stats=StatsProfile.of(iters)))
        diverse = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=64, stats=StatsProfile.of(
                iters, bindings={self._tasks_group(): 1.0})))
        assert plan_kind(diverse) == plan_kind(none) == "prefetch"
        assert diverse.est_cost_s == none.est_cost_s

    def test_param_site_amortization_floor_and_default(self):
        cm = CostModel(wilos_session().db, CostCatalog(SLOW_REMOTE),
                       ExecutionContext(batch_size=8, stats=StatsProfile.of(
                           bindings={self._tasks_group(): 0.01})))
        param_q = Select(Cmp("==", Col("t_role_id"), Param("r")),
                         Scan("tasks"))
        # observed 0.01 floors at 1/B (at most one fetch per distinct
        # binding, and at least one per batch)
        assert cm.param_site_amortization(param_q) == pytest.approx(1 / 8)
        # unobserved group: no amortization
        other = Select(Cmp("==", Col("r_rank"), Param("r")), Scan("roles"))
        assert cm.param_site_amortization(other) == 1.0
        # one-shot context: batching cannot help
        cm1 = CostModel(wilos_session().db, CostCatalog(SLOW_REMOTE),
                        ExecutionContext(batch_size=1, stats=StatsProfile.of(
                            bindings={self._tasks_group(): 0.01})))
        assert cm1.param_site_amortization(param_q) == 1.0

    def test_unrelated_binding_site_leaves_plans_hot(self):
        """A published diversity for a group the program doesn't contain
        never invalidates its plans (fingerprint restriction)."""
        from repro.core import param_group_key
        db = make_orders_customer_db(100, 5000)
        session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                               config=OptimizerConfig.preset("paper-exp1-3"))
        session.compile(make_p0())
        again = session.compile(make_p0(), context=ExecutionContext(
            stats=StatsProfile.of(
                bindings={param_group_key(("tasks",)): 0.1})))
        assert again.from_cache

    def test_program_param_sites(self):
        from repro.api import program_param_sites
        assert program_param_sites(make_wilos_e()) == (self._tasks_group(),)
        assert self._tasks_group() in program_param_sites(make_scan())
        assert program_param_sites(make_p0()) == ()      # binding-free

    def test_report_carries_binding_diversity(self):
        session = wilos_session()
        exe = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=64, stats=StatsProfile.of(
                bindings={self._tasks_group(): 0.25})))
        assert exe.report.binding_diversity == {self._tasks_group(): 0.25}
        assert "binding-diversity~0.25" in exe.report.describe()

    def test_serving_loop_flips_both_ways_end_to_end(self):
        """The full closed loop (issue acceptance): serve W_E at
        batch_size=8. Registration (no observations) picks the prefetch
        plan. A phase of IDENTICAL worklists publishes iters=1 and
        d=1/8 -> the σ plan wins the context recompile (with iters alone
        prefetch would still win: the flip is diversity-driven). A phase
        of fully DIVERSE worklists pushes the published mean back up ->
        the prefetch plan returns. Every response stays bit-identical to
        uncached execution."""
        session = wilos_session()
        rt = ServingRuntime(session, batch_size=8, drift_threshold=1e9)
        rt.register(make_wilos_e())
        assert plan_kind(rt.executable("W_E")) == "prefetch"

        identical = [("W_E", {"worklist": [1]})] * 16
        responses = rt.serve(identical)
        assert plan_kind(rt.executable("W_E")) == "query"   # flip #1
        assert rt.context_recompiles >= 1
        # iters alone (no diversity) would NOT have flipped at batch 8:
        iters_only = session.compile(make_wilos_e(), context=ExecutionContext(
            batch_size=8, stats=StatsProfile.of({we_loop_site(): 1.0})))
        assert plan_kind(iters_only) == "prefetch"
        published = rt.feedback.telemetry()["binding_sites"]
        assert published[self._tasks_group()]["published"] == \
            pytest.approx(1 / 8)

        diverse = [("W_E", {"worklist": [i % 20]}) for i in range(16)]
        responses += rt.serve(diverse)
        assert plan_kind(rt.executable("W_E")) == "prefetch"  # flip #2
        # bit-identical to uncached execution, throughout both phases
        for (name, params), r in zip(identical + diverse, responses):
            assert r.outputs == session.execute(make_wilos_e(),
                                                **params).outputs


# --------------------------------------------------------------------------
# Context in plan identity
# --------------------------------------------------------------------------

class TestContextKeys:
    def test_program_sites_finds_while_and_collection_loops(self):
        assert scan_while_site() in program_sites(make_scan())
        assert we_loop_site() in program_sites(make_wilos_e())
        assert program_sites(make_p0()) == ()  # query-source loop only

    def test_distinct_batch_sizes_distinct_cache_entries(self):
        session = wilos_session()
        a = session.compile(make_scan(), context=ExecutionContext(batch_size=1))
        b = session.compile(make_scan(), context=ExecutionContext(batch_size=64))
        assert not a.from_cache and not b.from_cache
        assert session.memo_runs == 2
        # repeat compiles under each context hit their own entries
        assert session.compile(make_scan(),
                               context=ExecutionContext(batch_size=1)).from_cache
        assert session.compile(make_scan(),
                               context=ExecutionContext(batch_size=64)).from_cache

    def test_plan_store_keeps_contexts_apart(self, tmp_path):
        session = wilos_session(plan_store=str(tmp_path / "plans"))
        session.compile(make_scan(), context=ExecutionContext(batch_size=1))
        session.compile(make_scan(), context=ExecutionContext(batch_size=64))
        assert len(session.plan_store) == 2
        # a second session warm-starts per context from disk
        warm = wilos_session(plan_store=str(tmp_path / "plans"))
        hit = warm.compile(make_scan(), context=ExecutionContext(batch_size=64))
        assert hit.from_cache and plan_kind(hit) == "prefetch"

    def test_report_carries_context_fingerprint(self):
        session = wilos_session()
        exe = session.compile(make_scan(),
                              context=ExecutionContext(batch_size=64))
        assert exe.report.context_fp[1] == 64
        assert "batch=64" in exe.report.describe()

    def test_context_validation(self):
        with pytest.raises(ValueError):
            ExecutionContext(batch_size=0)


# --------------------------------------------------------------------------
# Acceptance: user rules via the public RuleSet API
# --------------------------------------------------------------------------

class TestRuleSet:
    def _limit_rule(self):
        """A user transformation: rewrite a binding-free fold-over-Scan
        source into a fold over LIMIT(n) of it — sound only under
        application-specific knowledge (the program consumes at most n
        rows), which is exactly why it belongs in user space, not core."""
        from repro.core.fir import FFoldE, FQueryE
        from repro.relational.algebra import Limit

        @cobra_rule("user-limit", match="slot-project",
                    doc="fold over Scan(R) -> fold over LIMIT 3 of it")
        def user_limit(memo, and_id, ctx):
            s = slot_view(memo, and_id)
            if s is None or s.prefetches:
                return 0
            fold = s.fold
            if not (isinstance(fold.source, FQueryE)
                    and isinstance(fold.source.query, Scan)):
                return 0
            new_fold = FFoldE(fold.func, fold.init,
                              FQueryE(Limit(3, fold.source.query)),
                              fold.acc_names, fold.row_name)
            return add_slot_variant(memo, and_id, s.var, s.index, new_fold,
                                    ctx, fold)

        return user_limit

    def test_user_rule_fires_and_wins_without_touching_core(self):
        """Acceptance: the rule registered through the public API produces
        the winning plan — a LIMIT appears in the compiled program, which no
        built-in rule can emit."""
        from repro.programs import make_wilos_b
        rules = RuleSet.default().with_rule(self._limit_rule())
        session = CobraSession(
            make_wilos_db(300, ratio=10), CostCatalog(SLOW_REMOTE),
            config=OptimizerConfig(exclude_rules=("T3",), rule_set=rules))
        exe = session.compile(make_wilos_b())
        assert "LIMIT 3" in repr(exe.program.body)
        baseline = CobraSession(
            make_wilos_db(300, ratio=10), CostCatalog(SLOW_REMOTE),
            config=OptimizerConfig.preset("paper-exp1-3")).compile(
                make_wilos_b())
        assert exe.est_cost_s < baseline.est_cost_s

    def test_rule_identity_in_cache_key(self):
        """Two configs differing only in a registered user rule must not
        share plan-cache entries."""
        rules = RuleSet.default().with_rule(self._limit_rule())
        base = OptimizerConfig.preset("paper-exp1-3")
        custom = dataclasses.replace(base, rule_set=rules)
        assert base.cache_key() != custom.cache_key()
        assert ("user-limit" in [fp[0] for fp in custom._rules_key()])

    def test_ruleset_registry_operations(self):
        rs = RuleSet.default()
        assert "T5" in rs and "toFIR" in rs.names()
        assert len(rs.without("T5")) == len(rs) - 1
        sub = rs.subset("toFIR", "T5")
        assert sub.names() == ("toFIR", "T5")
        with pytest.raises(KeyError):
            rs.subset("nope")
        with pytest.raises(KeyError):
            rs.rule("nope")
        # decorator registration form
        @rs.register(name="noop-rule", match="loop")
        def noop_rule(memo, and_id, ctx):
            return 0
        assert "noop-rule" in rs and rs.rule("noop-rule").revision != "builtin"
        assert "noop-rule" in rs.describe()

    def test_default_is_a_fresh_copy(self):
        a = RuleSet.default()
        b = RuleSet.default()
        a.register(self._limit_rule())
        assert "user-limit" in a and "user-limit" not in b

    def test_config_rejects_non_ruleset(self):
        cfg = OptimizerConfig(rule_set="not a ruleset")
        with pytest.raises(TypeError):
            cfg.resolve_rules()

    def test_config_name_filters_within_custom_set(self):
        rules = RuleSet.default().with_rule(self._limit_rule())
        cfg = OptimizerConfig(rule_set=rules, exclude_rules=("user-limit",))
        assert "user-limit" not in cfg.rule_names()
        cfg2 = OptimizerConfig(rule_set=rules, rules=("toFIR", "user-limit"))
        assert cfg2.rule_names() == ("toFIR", "user-limit")


# --------------------------------------------------------------------------
# Satellite: declared before=/after= ordering constraints on rules
# --------------------------------------------------------------------------

class TestRuleOrdering:
    def _noop(self, name, **kw):
        @cobra_rule(name, match="loop", **kw)
        def fn(memo, and_id, ctx):
            return 0
        return fn

    def test_before_reorders_against_registry_order(self):
        rs = RuleSet()
        rs.register(self._noop("b"))
        rs.register(self._noop("a", before=("b",)))
        assert [r.name for r in rs.rules()] == ["b", "a"]      # registry
        assert [r.name for r in rs.resolve()] == ["a", "b"]    # resolved
        assert [r.name for r in rs.dag_rules()] == ["a", "b"]

    def test_after_reorders_and_stability(self):
        """Unconstrained rules keep their relative registry positions."""
        rs = RuleSet()
        for n in ("r1", "r2", "r3"):
            rs.register(self._noop(n))
        rs.register(self._noop("early", after=()))
        rs.register(self._noop("r1follower", after=("r1",)))
        assert [r.name for r in rs.resolve()] == \
            ["r1", "r2", "r3", "early", "r1follower"]
        rs2 = RuleSet()
        rs2.register(self._noop("late", after=("z",)))
        rs2.register(self._noop("z"))
        assert [r.name for r in rs2.resolve()] == ["z", "late"]

    def test_cycle_raises_clear_error(self):
        rs = RuleSet()
        rs.register(self._noop("x", before=("y",)))
        rs.register(self._noop("y", before=("x",)))
        with pytest.raises(ValueError, match="cycle"):
            rs.resolve()

    def test_constraints_on_absent_rules_ignored(self):
        """A rule may order itself against an optional/excluded peer."""
        rs = RuleSet()
        rs.register(self._noop("solo", before=("not-registered",),
                               after=("also-missing",)))
        assert [r.name for r in rs.resolve()] == ["solo"]
        # selection restricted to a subset ignores cross-subset edges too
        rs.register(self._noop("other", after=("solo",)))
        assert [r.name for r in rs.resolve(["other"])] == ["other"]

    def test_config_resolution_honors_constraints(self):
        """OptimizerConfig.resolve_rules goes through the topological sort:
        a user rule declaring after="T5" fires after T5 even though
        with_rule appends it... and one declaring before="toFIR" jumps the
        whole built-in pipeline (it must sit in the `normalize` phase to do
        so — ordering never crosses phase boundaries)."""
        first = self._noop("user-first", before=("toFIR",), phase="normalize")
        rules = RuleSet.default().with_rule(first)
        cfg = OptimizerConfig(rule_set=rules)
        names = [r.name for r in cfg.resolve_rules()]
        assert names.index("user-first") < names.index("toFIR")
        # the constrained set still compiles programs end to end
        session = CobraSession(make_wilos_db(100, ratio=10),
                               CostCatalog(SLOW_REMOTE), config=cfg)
        assert session.compile(make_wilos_e()).run(worklist=[1]).outputs

    def test_duplicate_selection_dedups_not_false_cycle(self):
        """A repeated name in the selection must resolve cleanly (first
        position wins), not misreport an empty 'cycle'."""
        rs = RuleSet()
        rs.register(self._noop("a", after=("b",)))
        rs.register(self._noop("b"))
        assert [r.name for r in rs.resolve(["a", "a", "b"])] == ["b", "a"]
        assert [r.name for r in rs.resolve(["b", "a", "b"])] == ["b", "a"]

    def test_cycle_surfaces_through_config(self):
        rs = RuleSet()
        rs.register(self._noop("p", after=("q",)))
        rs.register(self._noop("q", after=("p",)))
        with pytest.raises(ValueError, match="cycle"):
            OptimizerConfig(rule_set=rs).resolve_rules()

    def test_describe_shows_constraints(self):
        r = self._noop("shown", before=("T5",), after=("toFIR",))
        assert "before=['T5']" in r.describe()
        assert "after=['toFIR']" in r.describe()


# --------------------------------------------------------------------------
# Pluggable cost model
# --------------------------------------------------------------------------

class TestPluggableCostModel:
    def test_custom_cost_model_changes_winner(self):
        """A cost model that makes prefetching free forces the prefetch
        alternative to win where the built-in model keeps the aggregate
        query — the protocol is genuinely in control of plan choice."""
        class PrefetchLover(CostModel):
            revision = "test-1"

            def prefetch_cost(self, q):
                return 0.0

        session = wilos_session()
        builtin = session.compile(make_scan())
        custom = session.compile(
            make_scan(),
            config=dataclasses.replace(session.config,
                                       cost_model=PrefetchLover))
        assert plan_kind(builtin) == "query"
        assert plan_kind(custom) == "prefetch"

    def test_cost_model_identity_in_cache_key(self):
        class M(CostModel):
            pass

        base = OptimizerConfig()
        assert base.cache_key() != dataclasses.replace(
            base, cost_model=M).cache_key()

    def test_cost_model_receives_context(self):
        seen = {}

        class Spy(CostModel):
            def __init__(self, db, catalog, context=None):
                super().__init__(db, catalog, context)
                seen["context"] = self.context

        session = wilos_session()
        ctx = ExecutionContext(batch_size=7)
        session.compile(make_scan(), context=ctx,
                        config=dataclasses.replace(session.config,
                                                   cost_model=Spy))
        assert seen["context"] is ctx

    def test_non_class_cost_model_rejected(self):
        with pytest.raises(TypeError):
            OptimizerConfig(cost_model=42)

    def test_cost_model_gets_source_hash_revision(self):
        """Editing a custom model's body must change its cache identity
        (same safeguard user rules get); an explicit `revision` pins it."""
        class M(CostModel):
            pass

        key = OptimizerConfig(cost_model=M)._cost_model_key()
        assert key[-1] not in ("", None)

        class Pinned(CostModel):
            revision = "v7"

        assert OptimizerConfig(
            cost_model=Pinned)._cost_model_key()[-1] == "v7"

    def test_rules_override_path_keys_on_cost_model(self):
        """The back-compat `rules=` compile path must not collide across
        cost models."""
        class M(CostModel):
            pass

        session = wilos_session()
        rules = session.config.resolve_rules()
        a = session._cache_key(make_scan(), session.catalog, session.config,
                               rules)
        b = session._cache_key(make_scan(), session.catalog,
                               dataclasses.replace(session.config,
                                                   cost_model=M), rules)
        assert a != b

    def test_query_has_params_helper(self):
        from repro.relational.algebra import Not, Project
        assert not query_has_params(Scan("tasks"))
        assert query_has_params(
            Select(Cmp("==", Col("t_state"), Param("k")), Scan("tasks")))
        # params hiding under unary/odd scalar shapes must still be found
        # (misclassifying one as binding-free would wrongly amortize it)
        assert query_has_params(
            Select(Not(Cmp("==", Col("t_state"), Param("k"))), Scan("tasks")))
        assert query_has_params(
            Project((), Scan("tasks"), computed=(("v", Param("p")),)))


# --------------------------------------------------------------------------
# End-to-end: serving compiles a different plan than one-shot
# --------------------------------------------------------------------------

class TestServingContext:
    def test_serving_runtime_compiles_batch_aware_plan(self):
        """The same program, the same session: the serving runtime's
        registration compiles the batch-amortized winner while a plain
        one-shot compile keeps the per-iteration query."""
        session = wilos_session()
        one_shot = session.compile(make_scan())
        rt = ServingRuntime(session, batch_size=32)
        served = rt.register(make_scan())
        assert plan_kind(one_shot) == "query"
        assert plan_kind(served) == "prefetch"
        assert served.context.batch_size == 32

    def test_feedback_publishes_iterations_and_recompiles(self):
        """Observed while-iteration counts flow: interpreter -> batch
        observation log -> FeedbackController -> StatsProfile -> a
        context-driven recompile whose cost model uses the OBSERVED count."""
        session = wilos_session()
        rt = ServingRuntime(session, batch_size=2, feedback=True)
        rt.register(make_scan())
        # threshold never crossed -> the while runs all 5 states
        rt.serve([("SCAN", {"threshold": 1e9})] * 4)
        site = scan_while_site()
        profile = rt.feedback.stats_profile()
        assert profile.iters_for(site) == pytest.approx(5.0)
        assert rt.feedback.telemetry()["iters_publishes"] >= 1
        # the registered executable was recompiled under the observed stats
        exe = rt.executable("SCAN")
        assert exe.context.stats.iters_for(site) == pytest.approx(5.0)
        assert rt.context_recompiles >= 1

    def test_one_shot_session_unaffected_by_serving_plans(self):
        session = wilos_session()
        rt = ServingRuntime(session, batch_size=32)
        rt.register(make_scan())
        assert plan_kind(session.compile(make_scan())) == "query"


# --------------------------------------------------------------------------
# Context fingerprint defaults
# --------------------------------------------------------------------------

class TestContextHWProfile:
    def test_one_shot_fingerprint_default_single_source(self):
        from repro.api import PlanCacheKey, PlanReport
        from repro.core import ONE_SHOT
        assert PlanCacheKey("fp", (), (), 1).context_key == \
            ONE_SHOT.fingerprint()
        assert PlanReport("p", None, 0.0, 0, {}, 0.0,
                          None).context_fp == ONE_SHOT.fingerprint()
