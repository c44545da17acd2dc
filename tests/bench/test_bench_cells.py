"""Each cell end to end on the CPU at a small size: the served answers
match the plain reference, the check fails when the served path is broken
underneath, and the control (the reference in bfloat16) fails the limits.

The harness's look for a chip is skipped: ``run_cell`` is handed the CPU
device. No number of these runs is a device metric.
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.control import control_checks  # noqa: E402
from bench.registry import Benchmark  # noqa: E402

SMALL = {"exp1.p0-report": {"n_orders": 3000, "n_customers": 500},
         "wilos.we-zipf": {"n_tasks": 3000, "n_roles": 300}}
SEED = 2**31 + 77


def _run(cell, trace=False, seconds=0.4):
    import jax
    return harness.run_cell(Benchmark(ROOT), cell, SEED, seconds, trace,
                            jax.devices()[:1], sizes=SMALL[cell])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_served_answers_match_the_reference(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["checked"]["value"] >= 1
    assert set(out["metrics"]) == {"served_rps", "p50_latency_ms",
                                   "p95_latency_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    # the numbers compared are the last key of the result, and of stderr
    assert list(out)[-2:] == ["checks", "stderr"]
    assert out["stderr"][-1].startswith("check lost_requests")


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_traced_run_reads_the_program_layers(cell):
    out = _run(cell, trace=True)
    assert out["correct"]
    m = out["metrics"]
    for name in ("serving.batch_ms_per_req", "optimizer.compile_ms_per_req",
                 "sitecache.hit_rate", "server.round_trips_per_req",
                 "device.compiles_per_1k_req"):
        assert name in m, name
    assert (cell == "exp1.p0-report") == ("compiled.batch_share" in m)
    assert 0.0 <= m["sitecache.hit_rate"]["value"] <= 100.0
    # the CPU runs no device plane: nothing to read, so nothing reported
    assert "device.idle_share" not in m and "join_probe_roofline" not in m
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_the_warm_up_leaves_nothing_to_compile_in_the_window(cell):
    out = _run(cell)
    assert out["correct"]
    window = next(ln for ln in out["stderr"] if ln.startswith("window:"))
    assert window.endswith(" 0 compiles"), window
    assert out["stderr"][0].startswith("setup: ")


def test_every_slot_of_a_batch_is_checked():
    out = _run("exp1.p0-report")
    assert out["checks"]["checked"]["value"] == 8      # one a batch slot


def _alter_probe(monkeypatch):
    from repro.compiled import exec as cexec
    orig = cexec._probe

    def probe(cl, bk, keys):
        return np.roll(orig(cl, bk, keys), 1)

    monkeypatch.setattr(cexec, "_probe", probe)


def _alter_lookup(monkeypatch):
    from repro.relational.database import ClientEnv
    orig = ClientEnv.lookup_cache_all

    def lookup(self, table, column, key):
        rows = [dict(r) for r in orig(self, table, column, key)]
        if rows:
            rows[0]["t_hours"] += 1.0
        return rows

    monkeypatch.setattr(ClientEnv, "lookup_cache_all", lookup)


def _drop_half_of_each_batch(monkeypatch):
    from repro.runtime.serving import ServingRuntime
    orig = ServingRuntime.serve_batch

    def serve_batch(self, name, params):
        return orig(self, name, list(params)[:len(params) // 2])

    monkeypatch.setattr(ServingRuntime, "serve_batch", serve_batch)


ALTER = {"exp1.p0-report": _alter_probe, "wilos.we-zipf": _alter_lookup}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_an_answer_altered_where_it_is_produced_fails(cell, monkeypatch):
    ALTER[cell](monkeypatch)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["wrong_items"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_half_of_each_batch_left_out_fails(cell, monkeypatch):
    _drop_half_of_each_batch(monkeypatch)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["lost_requests"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_the_control_fails_the_limits_the_program_passes(cell):
    served = harness.prepare(Benchmark(ROOT), cell, SEED, False,
                             sizes=SMALL[cell])
    m = harness.measure(served, 0.4, False)
    config, program = served.config, served.program
    sound = harness.check(config, program, m.kept)
    control = control_checks(config, program, m.kept)
    assert harness.passes(sound)
    assert not harness.passes(control)
    assert control["wrong_items"]["value"] > 0


def test_run_refuses_a_machine_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "exp1.p0-report", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "exp1.p0-report", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
