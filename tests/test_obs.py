"""Observability layer: tracer spans, unified metrics, signals, EXPLAIN.

Issue acceptance:
  * ``scan_plan`` detects distinct bad-plan patterns on the naive example
    programs, and each signal DISAPPEARS after the optimizer's rewrite;
  * registry-backed counters reconcile bit-for-bit with the legacy
    telemetry dict views;
  * span trees stay well-nested through mid-stream ``analyze()`` /
    ``replace_table`` / drift-driven plan swaps;
  * tracing on vs off never changes outputs or the simulated clock;
  * ``explain()`` shows the rules that fired and per-site estimated-vs-
    observed counts; ``PlanReport`` carries tier + swap-guard outcome.
"""

import json

import numpy as np
import pytest

from repro.api import CobraSession, ExecutionContext, OptimizerConfig
from repro.core import CostCatalog
from repro.core.context import StatsProfile
from repro.api.cache import program_param_sites
from repro.obs import (MetricsRegistry, NoopTracer, Tracer, fmt_seconds,
                       merge_snapshots, render_triage, scan_plan)
from repro.obs.explain import q_error
from repro.programs import (make_m0, make_orders_customer_db, make_p0,
                            make_sales_db, make_scan, make_wilos_a,
                            make_wilos_db, make_wilos_e)
from repro.relational.database import FAST_LOCAL, SLOW_REMOTE
from repro.runtime import ServingRuntime


def paper_session(db, network=SLOW_REMOTE, **kw):
    return CobraSession(db, CostCatalog(network),
                        config=OptimizerConfig.preset("paper-exp1-3"), **kw)


def drifted_session(**kw):
    """Compile against 100 orders / 5000 customers; the caller bulk-loads
    the 4000/500 profile without ANALYZE to go stale (test_runtime idiom)."""
    session = paper_session(make_orders_customer_db(100, 5000), **kw)
    grown = make_orders_customer_db(4000, 500)
    return session, grown


# --------------------------------------------------------------------------
# MetricsRegistry
# --------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counters_and_labels(self):
        m = MetricsRegistry()
        m.inc("requests")
        m.inc("requests", 2)
        m.inc("requests", program="P0")
        assert m.value("requests") == 3
        assert m.value("requests", program="P0") == 1
        assert m.value("never_written") == 0

    def test_gauge_and_histogram(self):
        m = MetricsRegistry()
        m.gauge("stats_version", 7)
        m.gauge("stats_version", 9)
        assert m.gauge_value("stats_version") == 9
        for v in (1.0, 3.0, 2.0):
            m.observe("opt_time_s", v)
        h = m.histogram("opt_time_s")
        assert h["count"] == 3 and h["sum"] == 6.0
        assert h["min"] == 1.0 and h["max"] == 3.0

    def test_snapshot_and_diff(self):
        m = MetricsRegistry()
        m.inc("a")
        m.gauge("g", 1)
        older = m.snapshot()
        m.inc("a", 4)
        m.inc("b", program="P0")
        d = m.diff(older)
        assert d["a"] == 4
        assert d["b{program=P0}"] == 1
        assert "g" not in d                      # unchanged values drop out

    def test_ingest_and_merge(self):
        m = MetricsRegistry()
        m.ingest({"hits": 3, "misses": 1, "describe": "text"}, prefix="cache_")
        assert m.snapshot() == {"cache_hits": 3, "cache_misses": 1}
        snap = merge_snapshots(serving=m.snapshot())
        assert snap["serving_cache_hits"] == 3

    def test_fmt_seconds(self):
        assert fmt_seconds(None) == "—"
        assert fmt_seconds(2.5) == "2.50s"
        assert fmt_seconds(0.012) == "12.0ms"
        assert fmt_seconds(3e-5) == "30µs"

    def test_q_error_symmetric(self):
        assert q_error(100, 100) == 1.0
        assert q_error(100, 4000) == q_error(4000, 100) > 39


# --------------------------------------------------------------------------
# Registry-backed counters reconcile with legacy telemetry views
# --------------------------------------------------------------------------

class TestCounterReconciliation:
    def test_session_counters_are_registry_views(self):
        session = paper_session(make_orders_customer_db(200, 100))
        exe = session.compile(make_p0())
        exe.run()
        exe.run_batch([{}] * 3)
        t = session.telemetry
        for key in ("compile_calls", "memo_runs", "executions"):
            assert t[key] == getattr(session, key) \
                == session.metrics.value(key)
        assert session.executions == 4           # 1 run + batch of 3

    def test_serving_counters_reconcile_bit_for_bit(self):
        session = paper_session(make_orders_customer_db(200, 100))
        rt = ServingRuntime(session, batch_size=4)
        rt.register(make_p0())
        rt.serve([("P0", {})] * 8)
        t = rt.telemetry()
        for tkey, attr in (("requests_served", "requests_served"),
                           ("batches_run", "batches_run"),
                           ("recompiles", "recompiles"),
                           ("round_trips", "n_round_trips"),
                           ("simulated_s", "simulated_s")):
            assert t[tkey] == getattr(rt, attr) == rt.metrics.value(attr)
        ft = rt.feedback.telemetry()
        assert ft["stats_refreshes"] == rt.feedback.refreshes \
            == rt.feedback.metrics.value("refreshes")
        assert ft["observed_queries"] \
            == rt.feedback.metrics.value("observed_queries")

    def test_compiler_counters_reconcile(self):
        session = paper_session(make_orders_customer_db(300, 30), FAST_LOCAL)
        rt = ServingRuntime(session, batch_size=8, compile_hot_plans=2)
        rt.register(make_p0())
        rt.serve([("P0", {})] * 24)
        ct = rt.compiler.telemetry()
        for key in ("compiles", "compiled_batches", "interpreted_batches"):
            assert ct[key] == getattr(rt.compiler, key) \
                == rt.compiler.metrics.value(key)
        snap = rt.metrics_snapshot()
        assert snap["serving_compiled_compiles"] == ct["compiles"]
        assert snap["serving_requests_served"] == rt.requests_served
        assert snap["session_executions"] == session.executions
        assert snap["feedback_refreshes"] == rt.feedback.refreshes

    def test_external_increments_route_through_registry(self):
        session = paper_session(make_orders_customer_db(100, 50))
        session.plan_swaps_accepted = session.plan_swaps_accepted + 5
        assert session.metrics.value("plan_swaps_accepted") == 5


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------

class TestTracer:
    def test_manual_spans_well_nested(self):
        tr = Tracer()
        with tr.span("outer", workload="x"):
            with tr.span("inner"):
                pass
            tr.event("tick", n=1)
        assert tr.well_nested()
        (outer,) = tr.spans("outer")
        assert [c.name for c in outer.children] == ["inner", "tick"]
        assert outer.wall_s >= outer.children[0].wall_s
        assert "outer" in tr.render() and "inner" in tr.render()

    def test_export_jsonl(self, tmp_path):
        tr = Tracer()
        with tr.span("a"):
            tr.event("b")
        path = tmp_path / "trace.jsonl"
        assert tr.export_jsonl(str(path)) == 2
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        assert recs[0]["name"] == "a" and recs[0]["parent"] is None
        assert recs[1]["parent"] == recs[0]["id"]

    def test_compile_emits_phase_span_tree(self):
        tracer = Tracer()
        session = paper_session(make_orders_customer_db(100, 50),
                                tracer=tracer)
        session.compile(make_p0())
        assert tracer.well_nested()
        (comp,) = tracer.spans("compile")
        names = [c.name for c in comp.children]
        assert names[0] == "build-memo" and names[-1] == "codegen"
        assert "saturate" in names and "search" in names
        (sat,) = tracer.spans("saturate")
        assert sat.children and all(c.name == "saturate-round"
                                    for c in sat.children)

    def test_spans_stay_nested_through_drift_swap(self):
        """Mid-stream analyze()/replace_table/plan swap must not corrupt
        the span stack."""
        tracer = Tracer()
        session, grown = drifted_session(tracer=tracer)
        rt = ServingRuntime(session, batch_size=4, drift_threshold=3.0)
        rt.register(make_p0())
        rt.serve([("P0", {})] * 4)
        session.db.replace_table(grown.table("orders"))
        session.db.replace_table(grown.table("customer"))
        rt.serve([("P0", {})] * 8)
        assert rt.recompiles >= 1
        assert tracer.well_nested()
        assert tracer.spans("serving.serve") and tracer.spans("batch")
        verdicts = tracer.spans("swap-verdict")
        assert verdicts and verdicts[0].attrs["accepted"] is True
        # batch spans carry the simulated clock alongside the wall clock
        batches = tracer.spans("batch")
        assert any(b.sim_s and b.sim_s > 0 for b in batches)

    def test_tracing_never_changes_outputs_or_clock(self):
        """Bit-identity: the same stream served traced and untraced, through
        a drift-driven swap, on the interpreted and the compiled tier,
        yields equal outputs and simulated clocks; the traced run records
        the server, feedback, export and compiled-tier spans."""
        def run(tracer, hot):
            session, grown = drifted_session(tracer=tracer)
            rt = ServingRuntime(session, batch_size=4, drift_threshold=3.0,
                                compile_hot_plans=hot)
            rt.register(make_p0())
            out = list(rt.serve([("P0", {})] * 4))
            session.db.replace_table(grown.table("orders"))
            session.db.replace_table(grown.table("customer"))
            out += list(rt.serve([("P0", {})] * 8))
            return out, rt.simulated_s

        served = {"serving.serve", "batch", "serving.feedback",
                  "server.run", "server.analyze", "loop.export"}
        for hot, spans in ((0, served),
                           (1, served | {"compiled.loop", "compiled.probe"})):
            tracer = Tracer()
            traced_out, traced_sim = run(tracer, hot)
            plain_out, plain_sim = run(None, hot)
            assert traced_sim == plain_sim               # exact, not approx
            assert [r.outputs for r in traced_out] == \
                [r.outputs for r in plain_out]
            assert [r.simulated_s for r in traced_out] == \
                [r.simulated_s for r in plain_out]
            assert tracer.well_nested()
            assert spans <= {s.name for s in tracer.spans()}

    def test_feedback_span_holds_the_drift_recompile(self):
        """The recompile a drift triggers nests under the feedback span
        that found the drift, with the re-ANALYZE it ran."""
        tracer = Tracer()
        session, grown = drifted_session(tracer=tracer)
        rt = ServingRuntime(session, batch_size=4, drift_threshold=3.0)
        rt.register(make_p0())
        rt.serve([("P0", {})] * 4)
        session.db.replace_table(grown.table("orders"))
        session.db.replace_table(grown.table("customer"))
        rt.serve([("P0", {})] * 8)
        drifted = [s for s in tracer.spans("serving.feedback")
                   if s.attrs["drifted"]]
        assert drifted
        names = {c.name for s in drifted for c in s.children}
        assert {"server.analyze", "compile"} <= names

    def test_prefetch_lookups_open_client_spans(self):
        """W_E served by its prefetch plan on the exact interpreter: one
        ``client.lookup`` span per worklist key, inside its batch, with the
        rows it read."""
        tracer = Tracer()
        session = CobraSession(make_wilos_db(2000),
                               CostCatalog(FAST_LOCAL),
                               config=OptimizerConfig.preset("wilos"),
                               tracer=tracer)
        rt = ServingRuntime(session, batch_size=16)
        rt.register(make_wilos_e())
        rt.serve([("W_E", {"worklist": list(range(20))})])
        (batch,) = tracer.spans("batch")
        lookups = tracer.spans("client.lookup")
        assert len(lookups) == 20 and tracer.well_nested()
        (build,) = tracer.spans("client.cache_by_column")
        assert build.attrs == {"table": "tasks", "rows": 2000, "built": True}
        assert all(batch.wall_start <= s.wall_start <= s.wall_end
                   <= batch.wall_end for s in lookups)
        assert sum(s.attrs["n_rows"] for s in lookups) > 0

    def test_noop_tracer_records_nothing(self):
        session = paper_session(make_orders_customer_db(100, 50))
        assert isinstance(session.tracer, NoopTracer)
        session.compile(make_p0()).run()
        assert session.tracer.spans() == []


# --------------------------------------------------------------------------
# Host↔device transfer counters
# --------------------------------------------------------------------------

def _transfer_delta(before):
    from repro.obs.transfer import TRANSFERS
    return TRANSFERS.diff(before)


class TestTransferCounters:
    def test_row_read_counts_one_host_read_per_column(self):
        from repro.obs.transfer import TRANSFERS
        from repro.relational.table import Field, Schema, Table
        t = Table.from_columns(
            "t", Schema.of(Field("a", "int32"), Field("b", "float32"),
                           Field("c", "int32")),
            a=np.arange(10), b=np.ones(10), c=np.zeros(10))
        before = TRANSFERS.snapshot()
        assert t.row(3) == {"a": 3, "b": 1.0, "c": 0}
        assert _transfer_delta(before) == {
            "host_reads{site=table.row}": 3,
            "d2h_bytes{site=table.row}": 12}

    def test_interpret_probe_moves_4n_bytes_each_way(self):
        from collections import Counter
        from types import SimpleNamespace
        from repro.compiled.exec import _BuildKeys, _probe
        from repro.kernels import ops
        from repro.obs.transfer import TRANSFERS
        db = make_orders_customer_db(1000, 100)
        bk = _BuildKeys(db.table("customer"), "c_customer_sk")
        keys = np.asarray(db.table("orders").column("o_customer_sk"))
        cl = SimpleNamespace(backend="kernels", kernel_calls=Counter())
        state = ops.pallas_state()
        try:
            ops.use_pallas(True, interpret=True)
            first = _probe(cl, bk, keys)        # builds the direct table
            before = TRANSFERS.snapshot()
            again = _probe(cl, bk, keys)
        finally:
            ops.use_pallas(*state)
        assert cl.kernel_calls == {("join_probe", ops.INTERPRET): 2}
        assert (first == again).all()
        n = keys.shape[0]
        assert _transfer_delta(before) == {
            "h2d_bytes{site=compiled.probe}": 4 * n,
            "host_reads{site=compiled.probe}": 1,
            "d2h_bytes{site=compiled.probe}": 4 * n}

    def test_host_arrays_count_nothing(self):
        import jax.numpy as jnp
        from repro.obs.transfer import TRANSFERS, to_device, to_host
        host = np.arange(8, dtype=np.int32)
        dev = jnp.arange(8, dtype=jnp.int32)
        before = TRANSFERS.snapshot()
        assert to_host(host, "test") is host
        to_device(dev, jnp.float32, "test")
        assert _transfer_delta(before) == {}
        to_device(host, None, "test")
        to_host(dev, "test")
        assert _transfer_delta(before) == {
            "h2d_bytes{site=test}": 32, "host_reads{site=test}": 1,
            "d2h_bytes{site=test}": 32}

    def test_runtime_snapshot_surfaces_the_counters(self):
        session = paper_session(make_orders_customer_db(100, 50))
        rt = ServingRuntime(session, batch_size=4)
        rt.register(make_p0())
        before = rt.metrics_snapshot()
        rt.serve([("P0", {})] * 4)
        after = rt.metrics_snapshot()
        reads = [k for k in after if k.startswith("transfer_host_reads")]
        assert sum(after[k] - before.get(k, 0) for k in reads) > 0


# --------------------------------------------------------------------------
# Bad-plan signals: detected naive, gone after the rewrite
# --------------------------------------------------------------------------

class TestScanPlan:
    def test_p0_n_plus_one_detected_then_rewritten_away(self):
        found = scan_plan(make_p0())
        assert [s.kind for s in found] == ["n_plus_one"]
        assert found[0].severity == pytest.approx(0.8)
        session = paper_session(make_orders_customer_db(300, 600))
        assert session.compile(make_p0()).scan() == []

    def test_scan_query_in_while_detected_then_rewritten_away(self):
        found = scan_plan(make_scan())
        assert {s.kind for s in found} == {"query_in_while"}
        session = paper_session(make_wilos_db(300, ratio=10))
        exe = session.compile(make_scan(),
                              context=ExecutionContext(batch_size=16))
        assert "prefetch" in repr(exe.program.body)
        assert exe.scan() == []

    def test_wilos_a_unbatched_writes_detected(self):
        found = scan_plan(make_wilos_a())
        assert "unbatched_writes" in {s.kind for s in found}

    def test_wilos_e_n_plus_one_then_prefetch_rewrite(self):
        assert "n_plus_one" in {s.kind for s in scan_plan(make_wilos_e())}
        session = paper_session(make_wilos_db(300, ratio=10), FAST_LOCAL)
        exe = session.compile(make_wilos_e(),
                              context=ExecutionContext(batch_size=64))
        assert "prefetch" in repr(exe.program.body)
        assert exe.scan() == []

    def test_diverse_bindings_from_observed_stats(self):
        we = make_wilos_e()
        groups = program_param_sites(we)
        assert groups
        hostile = StatsProfile.of(bindings={g: 1.0 for g in groups})
        found = scan_plan(we, stats=hostile)
        assert "diverse_bindings" in {s.kind for s in found}
        friendly = StatsProfile.of(bindings={g: 0.1 for g in groups})
        assert "diverse_bindings" not in {
            s.kind for s in scan_plan(we, stats=friendly)}

    def test_interpreter_hot_loop_needs_heat(self):
        session = paper_session(make_wilos_db(200, ratio=10))
        exe = session.compile(make_wilos_a())
        cold = {s.kind for s in exe.scan()}
        assert "interpreter_hot_loop" not in cold
        for _ in range(3):
            exe.run()
        hot = {s.kind for s in exe.scan()}
        assert "interpreter_hot_loop" in hot

    def test_clean_program_yields_no_signals(self):
        assert scan_plan(make_m0()) == []

    def test_signals_rank_most_severe_first(self):
        sigs = scan_plan(make_wilos_a())
        assert [s.severity for s in sigs] == \
            sorted((s.severity for s in sigs), reverse=True)


# --------------------------------------------------------------------------
# EXPLAIN + PlanReport tier/swap fields + triage
# --------------------------------------------------------------------------

class TestExplainAndTriage:
    def test_explain_we_shows_rules_and_est_vs_observed(self):
        """Acceptance: explain() for W_E shows the rules that fired and
        per-site estimated-vs-observed counts."""
        session = paper_session(make_wilos_db(300, ratio=10), FAST_LOCAL)
        rt = ServingRuntime(session, batch_size=8, drift_threshold=1e9)
        rt.register(make_wilos_e())
        rt.serve([("W_E", {"worklist": [i % 4]}) for i in range(16)])
        text = rt.explain("W_E")
        assert "EXPLAIN W_E" in text
        assert "rules fired (winning plan):" in text
        assert "est " in text and "observed " in text
        assert "q-error" in text
        assert "tier: interpreter" in text

    def test_report_tier_after_hot_promotion(self):
        session = paper_session(make_orders_customer_db(300, 30), FAST_LOCAL)
        rt = ServingRuntime(session, batch_size=8, compile_hot_plans=2)
        rt.register(make_p0())
        exe = rt.executable("P0")
        assert exe.report.tier == "interpreter"
        rt.serve([("P0", {})] * 24)
        assert exe.report.tier == "compiled"
        assert "tier: compiled" in rt.explain("P0")

    def test_report_swap_fields_after_drift(self):
        session, grown = drifted_session()
        rt = ServingRuntime(session, batch_size=4, drift_threshold=3.0)
        rt.register(make_p0())
        session.db.replace_table(grown.table("orders"))
        session.db.replace_table(grown.table("customer"))
        rt.serve([("P0", {})] * 8)
        assert rt.recompiles >= 1
        r = rt.executable("P0").report
        assert r.swap_checked and r.swap_accepted is True
        assert r.swap_replayed > 0
        assert "swap-guard accepted" in rt.explain("P0")

    def test_triage_ranks_by_traffic_weighted_win(self):
        session, grown = drifted_session()
        session.db.add_table(make_sales_db(300).table("sales"))
        rt = ServingRuntime(session, batch_size=4, drift_threshold=3.0)
        rt.register(make_p0())
        rt.register(make_m0())
        session.db.replace_table(grown.table("orders"))
        session.db.replace_table(grown.table("customer"))
        rt.serve([("P0", {})] * 8 + [("M0", {})] * 4)
        rows = rt.triage()
        assert [r.name for r in rows][0] == "P0"     # drifted + most traffic
        p0, m0 = rows[0], next(r for r in rows if r.name == "M0")
        assert p0.drift > 3.0 and m0.drift == 1.0
        assert p0.score > m0.score
        assert abs(sum(r.share for r in rows) - 1.0) < 1e-9
        table = render_triage(rows)
        assert table.splitlines()[0].startswith("| program |")
        assert "P0" in table
        assert "drift" in p0.describe() and "score" in p0.describe()
