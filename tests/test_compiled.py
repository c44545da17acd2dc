"""The compiled execution tier: lowering, tier selection, promotion,
invalidation, and the anti-regression swap guard.

Issue acceptance:
  * compiled execution is BIT-IDENTICAL to interpreted execution — outputs
    AND the simulated clock / query / round-trip telemetry — for every
    example program, on every available backend;
  * identity survives concurrent ``analyze()`` and table writes landing
    mid-stream (epoch-keyed probe indices rebuild, artifacts invalidate);
  * ``CompileManager`` promotes a hot (program, plan, context) pair only
    after the configured number of interpreted invocations, caches the
    artifact content-addressed, and drops it when its tables drift;
  * regions outside the columnar vocabulary (``while`` guards, early
    exits, nested loops, update bodies) stay on the interpreter — the
    splicing is the fallback, never an error;
  * a drift-triggered plan swap is replayed against the last observed
    bindings and REJECTED when the old plan is actually cheaper.
"""

import types

import numpy as np
import pytest

from repro.api import CobraSession, OptimizerConfig
from repro.compiled import (CompileManager, available_backends, lower_program,
                            resolve_backend)
from repro.core import CostCatalog
from repro.kernels import ops
from repro.programs import (make_orders_customer_db, make_p0, make_p2,
                            make_scan, make_wilos_a, make_wilos_b,
                            make_wilos_c, make_wilos_db)
from repro.relational.database import FAST_LOCAL, SLOW_REMOTE
from repro.runtime import ServingRuntime

from paper_programs import PROGRAMS


def session(db, network=SLOW_REMOTE):
    return CobraSession(db, CostCatalog(network))


def run_tier(name, tier, backend=None, monkeypatch=None):
    make, mkdb, params = PROGRAMS[name]
    sess = session(mkdb())
    exe = sess.compile(make())
    if backend is not None and monkeypatch is not None:
        monkeypatch.setenv("REPRO_COMPILED_BACKEND", backend)
    return exe.run_batch(params, tier=tier)


def assert_batches_identical(a, b):
    assert a.n_queries == b.n_queries
    assert a.n_round_trips == b.n_round_trips
    assert a.simulated_s == b.simulated_s
    assert len(a.results) == len(b.results)
    for ra, rb in zip(a.results, b.results):
        assert ra.outputs == rb.outputs
        assert ra.simulated_s == rb.simulated_s
        assert ra.n_queries == rb.n_queries
        assert ra.n_round_trips == rb.n_round_trips


# --------------------------------------------------------------------------
# Tier parity: compiled == interpreted, bit for bit and tick for tick
# --------------------------------------------------------------------------

class TestTierParity:
    @pytest.mark.parametrize("name", sorted(PROGRAMS))
    @pytest.mark.parametrize("backend", available_backends())
    def test_program_identical_across_tiers(self, name, backend, monkeypatch):
        interp = run_tier(name, "interpreter")
        compiled = run_tier(name, "compiled", backend, monkeypatch)
        assert interp.tier == "interpreter"
        assert compiled.tier == "compiled"
        assert_batches_identical(interp, compiled)

    def test_backends_agree(self, monkeypatch):
        if len(available_backends()) < 2:
            pytest.skip("only one backend importable")
        a = run_tier("P0", "compiled", "kernels", monkeypatch)
        b = run_tier("P0", "compiled", "numpy", monkeypatch)
        assert_batches_identical(a, b)

    def test_identity_under_mid_stream_analyze_and_write(self):
        """An analyze() and a table write landing BETWEEN compiled batches
        must leave compiled results identical to a pure-interpreter twin
        seeing the same interleaving (epoch keys rebuild probe indices)."""
        outs = {}
        for tier in ("interpreter", "compiled"):
            db = make_orders_customer_db(300, 30)
            sess = session(db)
            exe = sess.compile(make_p0())
            batches = [exe.run_batch([{}] * 3, tier=tier)]
            db.analyze()                                  # stats epoch moves
            batches.append(exe.run_batch([{}] * 3, tier=tier))
            orders = db.table("orders")
            db.replace_table(orders.head(orders.nrows - 20))
            batches.append(exe.run_batch([{}] * 3, tier=tier))
            outs[tier] = batches
        for a, b in zip(outs["interpreter"], outs["compiled"]):
            assert_batches_identical(a, b)

    def test_epoch_moves_rebuild_probe_index(self):
        # the raw (unoptimized) P0: its navigation loop lowers to the nav
        # hook, whose probe index is epoch-cached
        from repro.runtime import BatchClientEnv
        db = make_orders_customer_db(200, 20)
        lowered = lower_program(make_p0())
        assert lowered.n_columnar >= 1
        cl = next(iter(lowered._loops.values()))
        env = BatchClientEnv(db, SLOW_REMOTE)
        lowered.run(env)
        first = cl.index_rebuilds
        assert first >= 1                       # cold index built once
        lowered.run(env)
        assert cl.index_rebuilds == first       # warm: epoch unchanged
        db.analyze("customer")
        lowered.run(env)
        assert cl.index_rebuilds > first        # epoch moved: rebuilt


# --------------------------------------------------------------------------
# Lowering: verdicts, tiered fallback, backend resolution
# --------------------------------------------------------------------------

class TestLowering:
    def test_scan_keeps_while_on_interpreter(self):
        sess = session(make_wilos_db(100))
        exe = sess.compile(make_scan())
        lowered = exe.lower()
        # the while guard and early exit are interpreter regions, yet the
        # program still runs (splicing fallback), so lowering never errors
        assert lowered.interpreter_regions >= 1

    def test_nested_loops_lower_to_zero_columnar(self):
        sess = session(make_wilos_db(100))
        exe = sess.compile(make_wilos_c())
        lowered = exe.lower()
        # W_C's winner either rewrites the nest away (columnar loop) or
        # keeps it (0 columnar loops) — both are valid; what matters is
        # that nested regions never get a columnar binding they can't run
        assert lowered.n_columnar >= 0
        assert "columnar loop" in lowered.describe()

    def test_executable_lower_is_memoized(self):
        sess = session(make_orders_customer_db(100, 10))
        exe = sess.compile(make_p0())
        assert exe.lower() is exe.lower()

    def test_resolve_backend_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_COMPILED_BACKEND", "numpy")
        assert resolve_backend() == "numpy"
        # explicit request beats the environment
        assert resolve_backend(available_backends()[0]) == \
            available_backends()[0]

    def test_resolve_backend_rejects_unknown(self):
        with pytest.raises(ValueError):
            resolve_backend("cuda")

    def test_run_batch_rejects_unknown_tier(self):
        sess = session(make_orders_customer_db(50, 5))
        exe = sess.compile(make_p0())
        with pytest.raises(ValueError):
            exe.run_batch([{}], tier="gpu")


# --------------------------------------------------------------------------
# Kernel calls: counted under the implementation that actually ran
# --------------------------------------------------------------------------

class TestKernelCalls:
    # (program, rules, kernel the compiled loop reaches): P2's prefetch
    # lookups and the unrewritten P0's ORM navigation both probe; the
    # unrewritten W_B's integer count folds (rules=() keeps a program as
    # written)
    CASES = {
        "lookup": (make_p2, None, "join_probe"),
        "navigation": (make_p0, (), "join_probe"),
        "fold": (make_wilos_b, (), "segment_reduce"),
    }

    def run(self, case, monkeypatch=None, backend=None):
        make, rules, kernel = self.CASES[case]
        db = make_wilos_db(200) if case == "fold" \
            else make_orders_customer_db(300, 30)
        sess = session(db)
        config = None if rules is None else OptimizerConfig(rules=rules)
        exe = sess.compile(make(), config=config)
        if backend is not None:
            monkeypatch.setenv("REPRO_COMPILED_BACKEND", backend)
        interp = exe.run_batch([{}] * 2, tier="interpreter")
        comp = exe.run_batch([{}] * 2, tier="compiled")
        assert_batches_identical(interp, comp)
        return kernel, exe.lower().kernel_calls()

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_cpu_counts_reference_calls(self, case):
        kernel, calls = self.run(case)
        assert calls == {(kernel, ops.REF): 2}

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_numpy_backend_counts_reference_calls(self, case, monkeypatch):
        kernel, calls = self.run(case, monkeypatch, backend="numpy")
        assert calls == {(kernel, ops.REF): 2}

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_forced_kernels_count_interpret_calls(self, case):
        state = ops.pallas_state()
        try:
            ops.use_pallas(True, interpret=True)
            kernel, calls = self.run(case)
        finally:
            ops.use_pallas(*state)
        assert calls == {(kernel, ops.INTERPRET): 2}

    def test_keys_outside_the_rule_count_as_reference(self):
        # a duplicate build key: no direct-address table can hold it, so
        # even with the kernel forced on the probe takes the reference
        db = make_orders_customer_db(300, 30)
        cust = db.table("customer")
        db.replace_table(cust.take(np.r_[np.arange(cust.nrows), 0]))
        exe = session(db).compile(make_p2())
        state = ops.pallas_state()
        try:
            ops.use_pallas(True, interpret=True)
            interp = exe.run_batch([{}], tier="interpreter")
            comp = exe.run_batch([{}], tier="compiled")
        finally:
            ops.use_pallas(*state)
        assert_batches_identical(interp, comp)
        assert exe.lower().kernel_calls() == {("join_probe", ops.REF): 1}


# --------------------------------------------------------------------------
# CompileManager: promotion, artifact cache, invalidation
# --------------------------------------------------------------------------

class TestCompileManager:
    def _exe(self, n=150):
        sess = session(make_orders_customer_db(n, 15))
        return sess, sess.compile(make_p0())

    def test_promotion_threshold(self):
        sess, exe = self._exe()
        mgr = CompileManager(sess, threshold=3)
        assert mgr.lowered_for(exe, n_invocations=1) is None
        assert mgr.lowered_for(exe, n_invocations=1) is None
        lowered = mgr.lowered_for(exe, n_invocations=1)
        assert lowered is not None and lowered.n_columnar >= 1
        assert mgr.compiles == 1
        # further calls hit the artifact cache, no recompile
        assert mgr.lowered_for(exe) is lowered
        assert mgr.compiles == 1

    def test_batch_heat_promotes_immediately(self):
        sess, exe = self._exe()
        mgr = CompileManager(sess, threshold=4)
        # one 8-invocation batch crosses the threshold on its own
        assert mgr.lowered_for(exe, n_invocations=8) is not None

    def test_invalidate_tables_drops_artifact_and_heat(self):
        sess, exe = self._exe()
        mgr = CompileManager(sess, threshold=1)
        assert mgr.lowered_for(exe) is not None
        assert mgr.invalidate_tables(["orders"]) >= 1
        # artifact gone AND heat reset: next call starts cold again at
        # threshold 2
        mgr.threshold = 2
        assert mgr.lowered_for(exe, n_invocations=1) is None

    def test_zero_columnar_lowering_cached_as_noop(self):
        sess = session(make_wilos_db(80))
        exe = sess.compile(make_wilos_a())       # mutating nest: no columnar
        lowered = exe.lower()
        if lowered.n_columnar:
            pytest.skip("winner lowered W_A to a columnar form")
        mgr = CompileManager(sess, threshold=1)
        assert mgr.lowered_for(exe) is None
        assert mgr.noop_lowerings == 1
        assert mgr.lowered_for(exe) is None      # cached noop: not re-lowered
        assert mgr.noop_lowerings == 1
        assert mgr.telemetry()["noop_lowerings"] == 1

    def test_telemetry_keys(self):
        sess, exe = self._exe()
        mgr = CompileManager(sess, threshold=1)
        mgr.lowered_for(exe)
        t = mgr.telemetry()
        for k in ("backend", "threshold", "compiles", "compile_s_total",
                  "compiled_batches", "interpreted_batches",
                  "hot_candidates"):
            assert k in t


# --------------------------------------------------------------------------
# Serving integration: hot promotion, drift invalidation, swap guard
# --------------------------------------------------------------------------

class TestServingCompiledTier:
    def _runtime(self, compile_hot_plans=2, **kw):
        sess = session(make_orders_customer_db(300, 30),
                       network=FAST_LOCAL)
        rt = ServingRuntime(sess, batch_size=8,
                            compile_hot_plans=compile_hot_plans, **kw)
        rt.register(make_p0())
        return rt

    def test_hot_promotion_and_parity(self):
        reqs = [("P0", {})] * 24
        rt = self._runtime()
        out = rt.serve(reqs)
        t = rt.telemetry()
        assert t["compiled_compiles"] >= 1
        assert t["compiled_compiled_batches"] >= 1
        assert t["session_compiled_executions"] >= 8
        rt2 = self._runtime(compile_hot_plans=None)
        assert rt2.compiler is None
        out2 = rt2.serve(reqs)
        assert all(a.outputs == b.outputs and a.simulated_s == b.simulated_s
                   for a, b in zip(out, out2))

    def test_config_knob_enables_tier(self):
        sess = CobraSession(make_orders_customer_db(100, 10),
                            CostCatalog(FAST_LOCAL),
                            config=OptimizerConfig(compile_hot_plans=1))
        rt = ServingRuntime(sess, batch_size=4)
        assert rt.compiler is not None and rt.compiler.threshold == 1

    def test_compile_knob_not_in_cache_key(self):
        a = OptimizerConfig().cache_key()
        b = OptimizerConfig(compile_hot_plans=5).cache_key()
        assert a == b


class TestSwapGuard:
    def _feedback_session(self):
        db = make_orders_customer_db(400, 40)
        sess = session(db)                      # SLOW_REMOTE: N+1 is painful
        from repro.runtime.feedback import FeedbackController
        return sess, FeedbackController(sess, 3.0)

    def _fake_exe(self, program):
        return types.SimpleNamespace(program=program, source=program)

    def test_regressing_swap_rejected(self):
        sess, fb = self._feedback_session()
        good = self._fake_exe(sess.compile(make_p0()).program)  # optimized
        bad = self._fake_exe(make_p0())         # the raw N+1 original
        assert fb.validate_swap(bad, good, [{}]) is True
        assert fb.validate_swap(good, bad, [{}]) is False
        assert fb.swaps_rejected == 1 and fb.swaps_accepted == 1
        assert sess.plan_swaps_rejected == 1
        assert sess.plan_swaps_accepted == 1
        rejected = [s for s in fb.swap_log if not s["accepted"]]
        assert rejected and \
            rejected[0]["new_replay_s"] > rejected[0]["old_replay_s"]

    def test_no_bindings_accepts_without_replay(self):
        sess, fb = self._feedback_session()
        a = self._fake_exe(make_p0())
        b = self._fake_exe(sess.compile(make_p0()).program)
        assert fb.validate_swap(a, b, []) is True
        assert fb.swap_log[-1]["replayed"] == 0

    def test_mutating_program_accepts_without_replay(self):
        db = make_wilos_db(100)
        sess = session(db)
        from repro.runtime.feedback import FeedbackController
        fb = FeedbackController(sess, 3.0)
        wa = self._fake_exe(make_wilos_a())     # issues UPDATEs
        other = self._fake_exe(sess.compile(make_wilos_a()).program)
        version_before = db.site_epoch(("roles",))
        assert fb.validate_swap(wa, other, [{}]) is True
        assert fb.swap_log[-1]["replayed"] == 0
        # the guard must not have written the live database
        assert db.site_epoch(("roles",)) == version_before

    def test_serving_guarded_swap_counts_rejections(self):
        sess = session(make_orders_customer_db(300, 30))
        rt = ServingRuntime(sess, batch_size=4)
        rt.register(make_p0())
        rt.serve([("P0", {})] * 4)              # seeds the replay window
        bad = sess.compile(make_p0())
        bad = types.SimpleNamespace(program=make_p0(), source=make_p0(),
                                    from_cache=False)
        rt._guarded_swap("P0", bad)
        assert rt.swaps_rejected == 1
        assert rt.executable("P0") is not bad   # old plan kept serving
