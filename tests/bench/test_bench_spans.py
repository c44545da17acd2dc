"""Traced runs of each cell on the CPU at a small size: the program's spans
nest as the per-layer metrics expect, and every metric that reads them or
the transfer counters reads a value in the cells its ``workloads`` names.

No number of these runs is a device metric.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.registry import Benchmark  # noqa: E402

SMALL = {"exp1.p0-report": {"n_orders": 3000, "n_customers": 500},
         "wilos.we-zipf": {"n_tasks": 3000, "n_roles": 300}}
SEED = 2**31 + 91
# each cell's path from the served request down to the layer it spends in
PATHS = {"exp1.p0-report": [("serving.serve", "batch"),
                            ("batch", "compiled.loop"),
                            ("compiled.loop", "compiled.probe"),
                            ("compiled.loop", "loop.export")],
         "wilos.we-zipf": [("serving.serve", "batch"),
                           ("batch", "client.cache_by_column"),
                           ("batch", "client.lookup")]}
NEW_METRICS = ("device.host_reads_per_req", "device.transfer_mb_per_req",
               "compiled.probe_ms_per_req", "loop.export_ms_per_req",
               "client.lookup_ms_per_req")


def _edges(tracer):
    return {(parent.name, child.name) for parent in tracer.spans()
            for child in parent.children}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_traced_window_nests_the_new_spans(cell):
    served = harness.prepare(Benchmark(ROOT), cell, SEED, True,
                             sizes=SMALL[cell])
    harness.measure(served, 0.4, True)
    tracer = served.tracer
    assert tracer.well_nested()
    edges = _edges(tracer)
    for edge in PATHS[cell]:
        assert edge in edges, edge
    # the feedback loop runs after each batch, beside it
    assert ("serving.serve", "serving.feedback") in edges
    # the runtime's span is its own, inside the harness's annotation
    assert not tracer.spans("serve")


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_each_new_metric_reads_where_its_workloads_say(cell):
    import jax
    bench = Benchmark(ROOT)
    out = harness.run_cell(bench, cell, SEED, 0.4, True, jax.devices()[:1],
                           sizes=SMALL[cell])
    assert out["correct"]
    metrics = out["metrics"]
    wanted = {m.name for m in bench.metrics_for(cell, True)}
    for name in NEW_METRICS:
        assert (name in metrics) == (name in wanted), name
        if name in metrics:
            assert metrics[name]["value"] > 0, name


def test_tracing_cost_modes_switch_the_spans_and_restore_the_tracer():
    from bench.tracing_cost import MODES, measure_modes
    cell = "wilos.we-zipf"
    served = harness.prepare(Benchmark(ROOT), cell, SEED, True,
                             sizes=SMALL[cell])
    lines = list(measure_modes(served, 0.2, 1))
    assert [ln["mode"] for ln in lines] == list(MODES)
    for ln in lines:
        assert ln["completed"] > 0 and ln["failed"] == 0
        assert (ln["spans"] > 0) == (ln["mode"] in ("spans", "both"))
    rt = served.rt
    assert rt.tracer is served.tracer is rt.session.tracer is \
        rt.session.db.tracer
