"""Spans, compile counts and the device trace, reduced to numbers.

* :class:`AnnotatingTracer` is the program's own recording ``Tracer``
  (``repro.obs.trace``), handed to the session, that also opens a
  ``jax.profiler.TraceAnnotation`` for each span, so that the device
  trace says what the host was doing;
* :class:`CompileCounter` counts XLA backend compiles and persistent-cache
  loads while it is armed, through a ``jax.monitoring`` listener;
* :func:`capture` records the profiler's trace of a window;
  :func:`read_events` flattens it and :func:`reduce_trace` turns it into
  busy and idle time, per-program device time and the idle gaps by host
  annotation. The reduction works on plain :class:`Event` tuples, so it is
  tested on a small recorded trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from repro.obs.trace import Tracer

WINDOW_ANNOTATION = "bench.window"
# the harness's own host annotations
HARNESS_ANNOTATIONS = ("loadgen.wait", "serve", WINDOW_ANNOTATION)

# jax.monitoring events that mean a program was not found in memory
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


# --------------------------------------------------------------- spans

class _AnnotatedHandle:
    __slots__ = ("inner", "name", "ann")

    def __init__(self, inner, name: str):
        self.inner = inner
        self.name = name
        self.ann = None

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        return self.inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self.inner.__exit__(*exc)
        finally:
            self.ann.__exit__(*exc)


class AnnotatingTracer(Tracer):
    """The program's recording tracer, each span also a profiler
    annotation of the same name."""

    def span(self, name, sim_clock=None, **attrs):
        return _AnnotatedHandle(super().span(name, sim_clock, **attrs), name)


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def spans_in(tracer, name: str, t0: float, t1: float) -> List:
    """The tracer's spans called ``name`` that lie inside ``[t0, t1]``."""
    return [s for s in tracer.spans(name)
            if s.wall_end is not None and s.wall_start >= t0
            and s.wall_end <= t1]


# ------------------------------------------------------------ compiles

class CompileCounter:
    """Counts :data:`COMPILE_EVENTS` while armed. One listener per process:
    JAX offers no way to take a listener off again."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.armed = False
        self.count = 0

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax
            counter = cls()

            def listen(event, duration, **kw):
                if counter.armed and event in COMPILE_EVENTS:
                    counter.count += 1

            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._installed = counter
        return cls._installed

    @contextlib.contextmanager
    def counting(self):
        self.count = 0
        self.armed = True
        try:
            yield self
        finally:
            self.armed = False


# --------------------------------------------------------------- trace

class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the body into ``log_dir`` (host annotations and device
    activity; the Python call tracer stays off)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with annotation(WINDOW_ANNOTATION):
            yield
    finally:
        jax.profiler.stop_trace()


def read_events(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def program_name(event_name: str) -> str:
    """``jit_join_probe(42)`` -> ``jit_join_probe``."""
    return event_name.split("(", 1)[0].strip()


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text: ``%fusion.7 = (...) ...`` ->
    ``fusion.7``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def union_length(intervals: Iterable[Tuple[float, float]]) -> Tuple[
        float, List[Tuple[float, float]]]:
    """Total length of the union of ``[start, end)`` intervals, and the
    merged intervals in order."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


@dataclasses.dataclass
class DeviceReading:
    """The reduction of one traced window."""

    window_s: float
    busy_s: float                     # mean over the devices that ran
    n_devices: int
    program_s: Dict[str, float]       # device time per XLA program
    program_calls: Dict[str, int]
    op_s: Dict[str, float]            # device time per op
    idle_by_annotation: Dict[str, float]
    longest_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top(self, table: Dict[str, float], k: int = 10):
        return sorted(table.items(), key=lambda kv: -kv[1])[:k]


def _op_lines(events: Sequence[Event]) -> Dict[str, List[Event]]:
    """Per device plane, the events that are device operations: the
    ``XLA Ops`` line where the plane has one, else every line but the
    program and step summaries."""
    by_plane: Dict[str, Dict[str, List[Event]]] = defaultdict(
        lambda: defaultdict(list))
    for e in events:
        if is_device_plane(e.plane):
            by_plane[e.plane][e.line].append(e)
    out = {}
    for plane, lines in by_plane.items():
        if "XLA Ops" in lines:
            out[plane] = lines["XLA Ops"]
        else:
            out[plane] = [e for ln, evs in lines.items()
                          if ln not in ("XLA Modules", "Steps")
                          for e in evs]
    return out


def reduce_trace(events: Sequence[Event],
                 annotations: Iterable[str]) -> DeviceReading:
    """Busy and idle time of the devices inside the window annotation,
    device time per program (``XLA Modules`` line) and per op, and each
    idle gap of the first device named by the innermost host annotation
    (one of ``annotations``) open at its middle."""
    names = set(annotations) | {WINDOW_ANNOTATION}
    windows = [e for e in events if e.name == WINDOW_ANNOTATION
               and not is_device_plane(e.plane)]
    if not windows:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in the trace")
    w = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.end_ns

    def clip(e: Event) -> Tuple[float, float]:
        return max(e.start_ns, w0), min(e.end_ns, w1)

    ops = _op_lines(events)
    busy, merged_first = [], None
    op_s: Dict[str, float] = defaultdict(float)
    for plane in sorted(ops):
        total, merged = union_length(clip(e) for e in ops[plane])
        if not merged:
            continue
        busy.append(total)
        if merged_first is None:
            merged_first = merged
        for e in ops[plane]:
            s, t = clip(e)
            if t > s:
                op_s[op_name(e.name)] += (t - s) / 1e9
    program_s: Dict[str, float] = defaultdict(float)
    program_calls: Dict[str, int] = defaultdict(int)
    for e in events:
        if is_device_plane(e.plane) and e.line == "XLA Modules":
            s, t = clip(e)
            if t > s:
                program_s[program_name(e.name)] += (t - s) / 1e9
                program_calls[program_name(e.name)] += 1

    host = sorted((e for e in events if e.name in names
                   and not is_device_plane(e.plane)),
                  key=lambda e: e.start_ns)
    gaps: List[Tuple[float, float]] = []
    cur = w0
    for s, t in merged_first or []:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < w1:
        gaps.append((cur, w1))
    idle_by: Dict[str, float] = defaultdict(float)
    named_gaps = []
    for s, t in gaps:
        mid = (s + t) / 2
        inner = None
        for h in host:
            if h.start_ns <= mid <= h.end_ns and (
                    inner is None or h.dur_ns < inner.dur_ns):
                inner = h
        label = inner.name if inner is not None else "(none)"
        idle_by[label] += (t - s) / 1e9
        named_gaps.append((label, (t - s) / 1e9))
    named_gaps.sort(key=lambda g: -g[1])
    window_s = (w1 - w0) / 1e9
    return DeviceReading(
        window_s=window_s,
        busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
        n_devices=len(busy),
        program_s=dict(program_s), program_calls=dict(program_calls),
        op_s=dict(op_s), idle_by_annotation=dict(idle_by),
        longest_gaps=named_gaps[:10])
