"""The ``tpch.q4-streams`` cell on the CPU at a small size, with Q4's date
range served through the range index: the answers are correct, nothing
compiles in the window, every σ of the window reads the index, and the
semi-join still runs; and the metric that reads the index's counters.

The harness's look for a chip is skipped: ``run_cell`` is handed the CPU
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

CELL = "tpch.q4-streams"
# as tests/bench/test_bench_tpch_q4.py: a three-month window holds ~1,500
# of the 40,000 orders, well under an eighth
SMALL = {"n_orders": 40000, "n_customers": 3000, "n_parts": 4000,
         "n_suppliers": 200, "n_clerks": 20}
SEED = 2**31 + 1717
METRIC = "server.range_index_share"


@pytest.fixture(scope="module")
def traced():
    import jax
    return harness.run_cell(Benchmark(ROOT), CELL, SEED, 0.5, True,
                            jax.devices()[:1], sizes=SMALL)


def test_the_cell_reads_the_range_index(traced):
    assert traced["correct"], traced["checks"]
    assert traced["failed"] == 0
    window = next(ln for ln in traced["stderr"] if ln.startswith("window:"))
    assert window.endswith(" 0 compiles"), window
    metrics = traced["metrics"]
    assert metrics[METRIC]["value"] == 100.0
    assert metrics["server.semijoin_ms_per_req"]["value"] > 0
    assert metrics["server.round_trips_per_req"]["value"] == 1.0
    assert metrics["optimizer.exists_unnested_share"]["value"] == 100.0


def test_the_metric_is_listed_for_the_q4_cell_alone():
    bench = Benchmark(ROOT)
    (metric,) = [m for m in bench.per_layer if m.name == METRIC]
    assert metric.workloads == (CELL,)
    assert (metric.layer, metric.moves, metric.source) == \
        ("server", "served_rps", "program_counter")
    for cell in bench.cells:
        names = {m.name for m in bench.metrics_for(cell, True)}
        assert (METRIC in names) == (cell == CELL), cell


@pytest.mark.parametrize("before,after,share", [
    ({}, {}, None),
    ({"server_range_index_selects": 0, "server_range_full_selects": 0},
     {"server_range_index_selects": 0, "server_range_full_selects": 0},
     None),
    ({"server_range_index_selects": 2, "server_range_full_selects": 1},
     {"server_range_index_selects": 8, "server_range_full_selects": 3},
     75.0),
])
def test_the_reader_by_hand(before, after, share):
    """The share over the window's counter deltas; nothing to read where
    the program has no such counters (the parent of this metric) or
    evaluated no masked σ in the window."""
    reader = Benchmark(ROOT).load_reader(METRIC)
    run = harness.Run(cell=CELL, config=None, traffic={}, window=None,
                      setup_s=0.0, tracer=None, compiles=0, device=None,
                      peaks=None, before=before, after=after)
    assert reader.read(run) == share
