"""The ``client.index_reuse_rate`` reader: nothing where the program counts
no prefetch-cache index or nothing was counted in the window, the share of
reuses otherwise; and its entry in BENCHMARK.json."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.registry import Benchmark  # noqa: E402


def _counted(builds=None, reuses=None):
    """A runtime stand-in whose snapshot holds the counters given."""
    snap = {"serving_requests_served": 1}
    if builds is not None:
        snap.update(client_index_builds=builds, client_index_reuses=reuses)
    return SimpleNamespace(metrics_snapshot=lambda: dict(snap))


@pytest.mark.parametrize("before,after,want", [
    ((None, None), (None, None), None),     # a program without the counters
    ((1, 4), (1, 4), None),                 # nothing counted in the window
    ((1, 0), (2, 3), 75.0),
    ((1, 0), (1, 5), 100.0),
    ((0, 0), (2, 0), 0.0)])
def test_client_index_reuse_rate_reader(before, after, want):
    bench = Benchmark(ROOT)
    reader = bench.load_reader("client.index_reuse_rate")
    run = harness.Run(cell="wilos.we-zipf", config=None, traffic={},
                      window=None, setup_s=0.0, tracer=None, compiles=0,
                      device=None, peaks=None,
                      before=reader.snapshot(_counted(*before)),
                      after=reader.snapshot(_counted(*after)))
    assert reader.read(run) == want


def test_client_index_reuse_rate_entry_lists_both_cells():
    (metric,) = [m for m in Benchmark(ROOT).per_layer
                 if m.name == "client.index_reuse_rate"]
    assert metric.workloads == ("exp1.p0-report", "wilos.we-zipf")
    assert (metric.unit, metric.source, metric.layer, metric.moves) == (
        "%", "program_counter", "client", "p50_latency_ms")
