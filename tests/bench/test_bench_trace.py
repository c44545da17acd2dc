"""The reduction from a profiler trace to busy and idle time, device time
per program and idle gaps by host annotation, on hand-made events with
hand-computed answers."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from bench import tracing  # noqa: E402
from bench.tracing import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # ns


def _events():
    """A 100 ms window; the device runs ops at [10, 30) and [25, 40)
    (overlapping: busy 30 ms) and [70, 80) ms; one op starts before the
    window and one ends after it (clipped)."""
    return [
        Event(HOST, "python", "bench.window", 0 * MS, 100 * MS),
        Event(HOST, "python", "serve", 5 * MS, 50 * MS),
        Event(HOST, "python", "batch", 8 * MS, 40 * MS),
        Event(HOST, "python", "loadgen.wait", 55 * MS, 10 * MS),
        Event(HOST, "python", "PjitFunction(x)", 56 * MS, 1 * MS),
        Event(DEV, "XLA Modules", "jit_join_probe(7)", 10 * MS, 30 * MS),
        Event(DEV, "XLA Modules", "jit_take(3)", 70 * MS, 10 * MS),
        Event(DEV, "XLA Ops", "fusion.1", 10 * MS, 20 * MS),
        Event(DEV, "XLA Ops", "tpu_custom_call", 25 * MS, 15 * MS),
        Event(DEV, "XLA Ops", "gather", 70 * MS, 10 * MS),
        Event(DEV, "XLA Ops", "early", -5 * MS, 6 * MS),     # 1 ms inside
        Event(DEV, "XLA Ops", "late", 99 * MS, 5 * MS),      # 1 ms inside
        Event(DEV, "Steps", "step", 0 * MS, 100 * MS),       # not an op
    ]


def test_busy_idle_and_programs_by_hand():
    r = tracing.reduce_trace(_events(), ["serve", "batch", "loadgen.wait"])
    assert r.window_s == pytest.approx(0.100)
    # [-5,1)->[0,1) 1 ms, [10,40) 30 ms, [70,80) 10 ms, [99,104)->1 ms
    assert r.busy_s == pytest.approx(0.042)
    assert r.idle_share == pytest.approx(0.58)
    assert r.n_devices == 1
    assert r.program_s == {"jit_join_probe": pytest.approx(0.030),
                           "jit_take": pytest.approx(0.010)}
    assert r.program_calls == {"jit_join_probe": 1, "jit_take": 1}
    assert r.op_s["fusion.1"] == pytest.approx(0.020)
    assert r.op_s["early"] == pytest.approx(0.001)


def test_idle_gaps_named_by_innermost_annotation():
    r = tracing.reduce_trace(_events(), ["serve", "batch", "loadgen.wait"])
    # gaps: [1,10) mid 5.5 in serve>batch? batch starts at 8 -> serve;
    # [40,70) mid 55 -> serve ends at 55, loadgen.wait [55,65) is
    # shorter -> loadgen.wait; [80,99) mid 89.5 -> only the window
    assert r.idle_by_annotation == {
        "serve": pytest.approx(0.009),
        "loadgen.wait": pytest.approx(0.030),
        "bench.window": pytest.approx(0.019)}
    assert r.longest_gaps[0] == ("loadgen.wait", pytest.approx(0.030))


def test_without_a_window_annotation_the_trace_is_refused():
    events = [e for e in _events() if e.name != "bench.window"]
    with pytest.raises(ValueError):
        tracing.reduce_trace(events, [])


def test_a_plane_without_an_ops_line_counts_all_but_summaries():
    events = [Event(HOST, "python", "bench.window", 0, 10 * MS),
              Event("/device:TPU:1", "XLA Modules", "jit_f(1)", 0, 10 * MS),
              Event("/device:TPU:1", "stream 0", "op", 2 * MS, 3 * MS)]
    r = tracing.reduce_trace(events, [])
    assert r.busy_s == pytest.approx(0.003)


def test_union_length():
    total, merged = tracing.union_length([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert total == 4 and merged == [(0, 3), (5, 6)]


def test_program_name():
    assert tracing.program_name("jit_join_probe(1234)") == "jit_join_probe"
    assert tracing.program_name("fusion.3") == "fusion.3"


FIXTURE = Path(__file__).resolve().parent / "trace_v5e_p0_200k.json"


def _recorded():
    """Events of a trace recorded on one TPU v5e: two P0 requests at
    200,000 orders, each one ``join_probe`` call (device planes, and the
    host annotations of the harness and the program)."""
    return [Event(*e) for e in json.loads(FIXTURE.read_text())]


def test_recorded_v5e_trace_by_hand():
    events = _recorded()
    r = tracing.reduce_trace(events, ["serve", "batch"])
    w = next(e for e in events if e.name == "bench.window")
    ops = [e for e in events if e.line == "XLA Ops"]
    # busy by a second method: a 1 us raster of the window
    lo, n = int(w.start_ns // 1000), int(w.dur_ns // 1000) + 1
    bins = np.zeros(n, bool)
    for e in ops:
        a = max(int(e.start_ns // 1000), lo) - lo
        b = min(int(-(-e.end_ns // 1000)), lo + n) - lo
        bins[max(a, 0):max(b, 0)] = True
    assert r.window_s == pytest.approx(w.dur_ns / 1e9)
    assert r.busy_s == pytest.approx(bins.sum() * 1e-6, abs=40e-6)
    assert r.busy_s == pytest.approx(0.001455185, rel=1e-6)
    assert r.idle_share == pytest.approx(1 - 0.001455185 / 0.026145219,
                                         rel=1e-6)
    assert r.program_calls == {"jit_join_probe": 2}
    assert max(r.op_s, key=r.op_s.get) == "join_probe.1"
    # every gap lies inside the program's batch span or the serve call
    assert set(r.idle_by_annotation) <= {"batch", "serve"}
    assert sum(r.idle_by_annotation.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
