"""The ``tpch.q4-streams`` cell end to end on the CPU at a small size: the
served answers match the plain reference, the control (the counts in
bfloat16) fails the limits, and the cell's three per-layer metrics read
values.

The harness's look for a chip is skipped: ``run_cell`` is handed the CPU
device. No number of these runs is a device metric.
"""

import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, tracing  # noqa: E402
from bench.control import control_checks  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402
from bench.registry import Benchmark  # noqa: E402

CELL = "tpch.q4-streams"
# a three-month window holds ~1,500 orders, ~300 of each priority: counts
# past bfloat16's exact integers
SMALL = {"n_orders": 40000, "n_customers": 3000, "n_parts": 4000,
         "n_suppliers": 200, "n_clerks": 20}
SEED = 2**31 + 164
NEW_METRICS = ("server.semijoin_ms_per_req", "optimizer.exists_unnested_share",
               "semijoin_roofline")


@pytest.fixture(scope="module")
def traced():
    import jax
    return harness.run_cell(Benchmark(ROOT), CELL, SEED, 0.5, True,
                            jax.devices()[:1], sizes=SMALL)


def test_every_served_answer_is_checked_and_correct():
    import jax
    out = harness.run_cell(Benchmark(ROOT), CELL, SEED, 0.5, False,
                           jax.devices()[:1], sizes=SMALL)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["checks"]["checked"]["value"] == out["attempted"] > 0
    window = next(ln for ln in out["stderr"] if ln.startswith("window:"))
    assert window.endswith(" 0 compiles"), window


def test_the_control_fails_the_limits_the_program_passes():
    served = harness.prepare(Benchmark(ROOT), CELL, SEED, False, sizes=SMALL)
    m = harness.measure(served, 0.4, False)
    config, program = served.config, served.program
    assert harness.passes(harness.check(config, program, m.kept))
    control = control_checks(config, program, m.kept)
    assert not harness.passes(control)
    assert control["wrong_items"]["value"] > 0


def test_the_traced_run_reads_the_new_metrics(traced):
    assert traced["correct"]
    metrics = traced["metrics"]
    assert metrics["optimizer.exists_unnested_share"]["value"] == 100.0
    assert metrics["server.semijoin_ms_per_req"]["value"] > 0
    assert metrics["server.round_trips_per_req"]["value"] <= 1.0
    # the CPU runs no device plane: the roofline has nothing to read here
    assert "semijoin_roofline" not in metrics
    wanted = {m.name for m in Benchmark(ROOT).metrics_for(CELL, True)}
    assert set(NEW_METRICS) <= wanted


def test_the_roofline_reads_the_semijoin_program_and_counters():
    """The reader on a device reading as the chip's trace gives it: the
    jitted probe's device time and the window's semi-join row counters."""
    reader = Benchmark(ROOT).load_reader("semijoin_roofline")
    reading = tracing.DeviceReading(
        window_s=1.0, busy_s=0.5, n_devices=1,
        program_s={"jit__semijoin_probe": 0.01},
        program_calls={"jit__semijoin_probe": 10}, op_s={},
        idle_by_annotation={}, longest_gaps=[])
    run = harness.Run(
        cell=CELL, config=None, traffic={}, window=None, setup_s=0.0,
        tracer=None, compiles=0, device=reading,
        peaks=PEAKS["TPU v5 lite"],
        before={"server_semijoin_probe_rows": 0,
                "server_semijoin_build_rows": 0},
        after={"server_semijoin_probe_rows": 15_000_000,
               "server_semijoin_build_rows": 0})
    share = reader.read(run)
    # 10 probes of 1.5M keys: 75 MB in 10 ms against 819 GB/s
    assert share == pytest.approx(100 * 75e6 / 819e9 / 0.01)
    assert 0 < share <= 100
    assert reader.read(dataclasses.replace(run, device=None)) is None
