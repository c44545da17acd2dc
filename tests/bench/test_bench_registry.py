"""The harness finds configurations, traffic mixes and metric readers by
the names in BENCHMARK.json: a new cell, mix and metric are new files and
entries, with no file that is there edited. Also the peak table and the
probe's byte count."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, kernels, peaks  # noqa: E402
from bench.registry import Benchmark  # noqa: E402


def test_every_named_file_is_there():
    bench = Benchmark(ROOT)
    for name in bench.configs:
        cfg = bench.load_config(name)
        for fn in ("build_db", "programs"):
            assert callable(getattr(cfg.module, fn))
        for fn in ("generate", "reference", "control", "answer"):
            assert callable(getattr(cfg.reference, fn))
    for cell in bench.cells.values():
        assert bench.load_traffic(cell.traffic)["program"]
    for m in bench.end_to_end + bench.per_layer:
        assert callable(bench.load_reader(m.name).read)


def test_metrics_follow_their_workloads():
    bench = Benchmark(ROOT)
    e2e = [m.name for m in bench.metrics_for("wilos.we-zipf", False)]
    assert e2e == ["served_rps", "p50_latency_ms", "p95_latency_ms",
                   "setup_s"]
    per = [m.name for m in bench.metrics_for("wilos.we-zipf", True)]
    assert "join_probe_roofline" not in per and "device.idle_share" in per
    assert "join_probe_roofline" in [
        m.name for m in bench.metrics_for("exp1.p0-report", True)]


def test_a_new_cell_mix_and_metric_are_picked_up_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # added files: a mix and a metric reader
    (tmp_path / "bench" / "traffic" / "we-uniform-small.json").write_text(
        json.dumps({"loop": "open", "rate_rps": 300.0, "program": "W_E",
                    "params": {"worklist": {"key_list": {
                        "length": [1, 4],
                        "keys": {"uniform": True, "over": "n_roles"}}}},
                    "warmup_requests": 4, "check_sample": None}))
    (tmp_path / "bench" / "metrics" / "loadgen.batches_per_s.py").write_text(
        "def read(run):\n"
        "    return run.window.batches / run.window.seconds\n")
    # added entries
    spec["workloads"].append({"name": "wilos.we-uniform-small",
                              "config": "wilos-tasks-1m",
                              "traffic": "we-uniform-small", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "loadgen.batches_per_s",
                              "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "serving",
                              "moves": "served_rps",
                              "workloads": ["wilos.we-uniform-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    import jax
    out = harness.run_cell(Benchmark(tmp_path), "wilos.we-uniform-small", 5,
                           0.3, True, jax.devices()[:1],
                           sizes={"n_tasks": 2000, "n_roles": 200})
    assert out["correct"]
    assert out["metrics"]["loadgen.batches_per_s"]["value"] > 0
    # the existing cells still read only their own metrics
    assert "loadgen.batches_per_s" not in [
        m.name for m in Benchmark(tmp_path).metrics_for("wilos.we-zipf",
                                                        True)]


def test_a_new_reader_snapshots_the_runtime_across_the_window(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # an added reader of a program counter the harness does not know
    (tmp_path / "bench" / "metrics" / "serving.batches_run.py").write_text(
        "def snapshot(rt):\n"
        "    return rt.telemetry()\n"
        "\n"
        "def read(run):\n"
        "    return run.delta('batches_run')\n")
    spec["per_layer"].append({"name": "serving.batches_run", "unit": "1",
                              "better": "lower",
                              "source": "program_counter",
                              "layer": "serving", "moves": "served_rps",
                              "workloads": ["wilos.we-zipf"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    import jax
    out = harness.run_cell(Benchmark(tmp_path), "wilos.we-zipf", 6, 0.3,
                           True, jax.devices()[:1],
                           sizes={"n_tasks": 2000, "n_roles": 200})
    assert out["correct"]
    # one caller: one batch a request, the warm-up's left out
    assert out["metrics"]["serving.batches_run"]["value"] == out["attempted"]
    # the readers that ship take their counters the same way
    assert out["metrics"]["sitecache.hit_rate"]["value"] == 100.0


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_join_probe_bytes():
    # 1M int32 keys in, 1M int32 row indices out, 73k int32 slots read
    assert kernels.join_probe_bytes(1_000_000, 73_000) == \
        4_000_000 + 4_000_000 + 292_000
    v5e = peaks.peaks_for("TPU v5 lite")
    assert kernels.roofline_seconds(0.0, 8_292_000, v5e) == \
        pytest.approx(8_292_000 / 819e9)
    # operations bound it where they outweigh the bytes
    assert kernels.roofline_seconds(197e12, 1.0, v5e) == pytest.approx(1.0)


def test_roofline_reader_by_hand():
    from types import SimpleNamespace
    bench = Benchmark(ROOT)
    reader = bench.load_reader("join_probe_roofline")
    cfg = bench.load_config("exp1-orders-1m")
    dev = SimpleNamespace(program_s={"jit_join_probe": 0.010},
                          program_calls={"jit_join_probe": 2})
    run = SimpleNamespace(device=dev, config=cfg,
                          peaks=peaks.peaks_for("TPU v5 lite"))
    want = 100 * 2 * 8_292_000 / 819e9 / 0.010
    assert reader.read(run) == pytest.approx(want)
    dev.program_s, dev.program_calls = {}, {}
    assert reader.read(run) is None
