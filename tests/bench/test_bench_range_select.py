"""The metric ``server.range_select_ms_per_req`` on the CPU at small sizes:
it reads the ``server.range_select`` span (the host search of a σ's
two-sided integer bounds in the column's sorted index) in the
``tpch.q4-streams`` cell, and nothing in the cells that make no server
round trip.

The harness's look for a chip is skipped: each run is handed the CPU
device. No number of these runs is a device metric.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.registry import Benchmark  # noqa: E402

METRIC = "server.range_select_ms_per_req"
SPAN = "server.range_select"
CELL = "tpch.q4-streams"
# a three-month window holds ~1,500 of the 40,000 orders, under an eighth,
# so every request's σ on o_orderdate takes the range index
SMALL = {CELL: {"n_orders": 40000, "n_customers": 3000, "n_parts": 4000,
                "n_suppliers": 200, "n_clerks": 20},
         "exp1.p0-report": {"n_orders": 3000, "n_customers": 500},
         "wilos.we-zipf": {"n_tasks": 3000, "n_roles": 300}}
SEED = 2**31 + 1919


def test_the_metric_is_listed_for_the_q4_cell_alone():
    bench = Benchmark(ROOT)
    (metric,) = [m for m in bench.per_layer if m.name == METRIC]
    assert metric.workloads == (CELL,)
    assert (metric.layer, metric.moves, metric.source, metric.unit) == \
        ("server", "p50_latency_ms", "program_span", "ms")
    for cell in bench.cells:
        names = {m.name for m in bench.metrics_for(cell, True)}
        assert (METRIC in names) == (cell == CELL), cell


def test_the_span_reads_in_the_q4_cell():
    """Each request's σ on ``o_orderdate`` searches its bounds inside the
    span, which the metric reads per request."""
    import jax
    out = harness.run_cell(Benchmark(ROOT), CELL, SEED, 0.5, True,
                           jax.devices()[:1], sizes=SMALL[CELL])
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["metrics"][METRIC]["value"] > 0
    assert out["metrics"]["server.range_index_share"]["value"] == 100.0


@pytest.mark.parametrize("cell", ["exp1.p0-report", "wilos.we-zipf"])
def test_the_reader_reads_nothing_where_no_range_runs(cell):
    """The other cells make no server round trip, so their traced windows
    hold no span and the reader returns None there."""
    bench = Benchmark(ROOT)
    served = harness.prepare(bench, cell, SEED, True, sizes=SMALL[cell])
    m = harness.measure(served, 0.3, True)
    assert m.window.completed > 0
    assert not served.tracer.spans(SPAN)
    run = harness.Run(cell=cell, config=served.config, traffic=served.traffic,
                      window=m.window, setup_s=0.0, tracer=served.tracer,
                      compiles=m.compiles, device=None, peaks=None)
    assert bench.load_reader(METRIC).read(run) is None
