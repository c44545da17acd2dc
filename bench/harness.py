"""One run of one cell: build, warm up, measure, check, read the metrics.

:func:`run_cell` is the whole run after the device check; ``bench/run.py``
calls it on the chip, the tests call it on the CPU at small sizes.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import loadgen, tracing
from .registry import Benchmark, Config


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def require_devices(chips: int):
    """The devices the cell runs on: JAX's first ``chips`` TPU devices."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found platform "
                       f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoDevice(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or ``JAX_COMPILATION_CACHE_DIR``), for every program
    however short its compile, so that only a cell's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Run:
    """What a metric reader reads (``bench/metrics/<name>.py``). A reader
    that defines ``snapshot(rt)`` (``rt``: the ``ServingRuntime``) finds
    what it returned just before and just after the window in ``before``
    and ``after``."""

    cell: str
    config: Config
    traffic: dict
    window: loadgen.Window
    setup_s: float
    tracer: Optional[object]            # AnnotatingTracer in a traced run
    compiles: int
    device: Optional[tracing.DeviceReading]
    peaks: Optional[dict]
    before: Optional[dict] = None
    after: Optional[dict] = None

    def delta(self, key: str) -> Optional[float]:
        """Change of one counter of the reader's snapshot across the
        window, or None where the snapshot has no such counter."""
        if self.before is None or self.after is None \
                or key not in self.before or key not in self.after:
            return None
        return float(self.after[key]) - float(self.before[key])

    def spans(self, name: str) -> List:
        if self.tracer is None:
            return []
        return tracing.spans_in(self.tracer, name, self.window.t0,
                                self.window.t_end)


def _runtime(config: Config, traffic: dict):
    """The network, optimizer preset and ``ServingRuntime`` settings:
    the configuration's, with the traffic mix's overrides."""
    from repro.relational import database
    settings = dict(config.data["runtime"])
    settings.update(traffic.get("runtime", {}))
    network = getattr(database, settings.pop("network"))
    preset = settings.pop("optimizer_preset")
    return network, preset, settings


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def check(config: Config, program: str, kept, answer=None) -> Dict[str, dict]:
    """Compare each kept response with the plain reference: the numbers
    compared, each with its limit (exact answers: limit 0). ``answer``
    maps ``(params, outputs)`` to the answer to judge; by default the
    served one (the control puts its own in the program's place)."""
    ref = config.reference
    if answer is None:
        def answer(params, outputs):
            return ref.answer(outputs)
    wrong_answers = wrong_items = 0
    for _i, params, outputs in kept:
        want = ref.reference(config.columns, program, params)
        bad = mismatched_items(answer(params, outputs), want)
        wrong_items += bad
        wrong_answers += bad > 0
    return {"checked": {"value": len(kept), "limit": 1, "at_least": True},
            "wrong_answers": {"value": wrong_answers, "limit": 0},
            "wrong_items": {"value": wrong_items, "limit": 0}}


def mismatched_items(got: np.ndarray, want: np.ndarray) -> int:
    """Items that differ; an answer of the wrong length differs in all the
    positions of the longer one that the shorter cannot match."""
    n = min(len(got), len(want))
    return int(np.count_nonzero(got[:n] != want[:n])) \
        + abs(len(got) - len(want))


def passes(checks: Dict[str, dict]) -> bool:
    return all((c["value"] >= c["limit"]) if c.get("at_least")
               else (c["value"] <= c["limit"]) for c in checks.values())


@dataclasses.dataclass
class Served:
    """A cell's deployment, built and warmed up, ready for a window."""

    config: Config
    traffic: dict
    seed: int
    rt: object                  # the ServingRuntime, which holds the rest
    tracer: Optional[object]
    setup_phases: Dict[str, float]      # phase: seconds

    @property
    def program(self) -> str:
        return self.traffic["program"]


def prepare(bench: Benchmark, cell_name: str, seed: int, trace: bool, *,
            sizes: Optional[dict] = None) -> Served:
    """Set-up: make the data from the seed, build the tables on JAX's
    default device, register the programs with a ``CobraSession`` and a
    ``ServingRuntime``, and warm up on the traffic's own warm-up stream.
    ``sizes`` overrides the configuration's sizes (tests only)."""
    from repro.api import CobraSession, OptimizerConfig
    from repro.core import CostCatalog
    from repro.runtime import ServingRuntime

    marks = [time.perf_counter()]
    cell = bench.cell(cell_name)
    config = bench.load_config(cell.config)
    traffic = bench.load_traffic(cell.traffic)
    if sizes is not None:
        config.data = dict(config.data, sizes=dict(config.sizes, **sizes))
    config.columns = config.reference.generate(
        config.sizes, loadgen.rng_for(seed, loadgen.STREAM_DATA))
    marks.append(time.perf_counter())
    db = config.module.build_db(config.columns)
    marks.append(time.perf_counter())
    tracer = tracing.AnnotatingTracer() if trace else None
    network, preset, settings = _runtime(config, traffic)
    session = CobraSession(db, CostCatalog(network),
                           config=OptimizerConfig.preset(preset),
                           tracer=tracer)
    rt = ServingRuntime(session, **settings)
    for p in config.module.programs():
        rt.register(p)
    marks.append(time.perf_counter())
    n_warm = int(traffic.get("warmup_requests", 0))
    warm = loadgen.make_params(traffic, config.sizes,
                               loadgen.rng_for(seed, loadgen.STREAM_WARMUP),
                               n_warm)
    for lo in range(0, n_warm, rt.batch_size):
        rt.serve([(traffic["program"], p)
                  for p in warm[lo:lo + rt.batch_size]])
    marks.append(time.perf_counter())
    phases = dict(zip(("data", "tables", "register", "warm-up"),
                      np.diff(marks).tolist()))
    return Served(config, traffic, seed, rt, tracer, phases)


@dataclasses.dataclass
class Measured:
    window: loadgen.Window
    kept: list                      # (index, params, outputs) to check
    compiles: int
    snapshots: Dict[str, Tuple[dict, dict]]     # name: (before, after)
    device: Optional[tracing.DeviceReading]


def measure(served: Served, seconds: float, trace: bool, *,
            traffic: Optional[dict] = None,
            stream: int = loadgen.STREAM_WINDOW,
            snapshots: Optional[Dict[str, Callable]] = None) -> Measured:
    """One measured window of ``seconds`` on the served path, traced where
    ``trace`` is set. ``traffic`` overrides the cell's mix (a sweep's
    rate); ``stream`` picks the seed's stream of requests; ``snapshots``
    are taken of the runtime just before and just after the window."""
    traffic = traffic or served.traffic
    snapshots = snapshots or {}
    rt, sizes = served.rt, served.config.sizes
    sampler = loadgen.Sampler(
        traffic.get("check_sample"),
        loadgen.rng_for(served.seed, loadgen.STREAM_SAMPLE))
    window_rng = loadgen.rng_for(served.seed, stream)
    if traffic["loop"] == "open":
        offsets = loadgen.arrival_offsets(traffic, seconds, window_rng)
        params = loadgen.make_params(traffic, sizes, window_rng,
                                     len(offsets))

        def drive(annotate):
            return loadgen.drive_open(rt.serve, traffic, params, offsets,
                                      seconds, rt.batch_size, sampler,
                                      annotate)
    elif traffic["loop"] == "closed":
        drawn: List[dict] = []

        def params_for(i):
            while i >= len(drawn):
                drawn.extend(loadgen.make_params(traffic, sizes, window_rng,
                                                 loadgen.CLOSED_BLOCK))
            return drawn[i]

        def drive(annotate):
            return loadgen.drive_closed(rt.serve, traffic, params_for,
                                        rt.batch_size, seconds, sampler,
                                        annotate)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")

    counter = tracing.CompileCounter.install()
    gc.collect()
    before = {name: snap(rt) for name, snap in snapshots.items()}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with counter.counting():
            if trace:
                with tracing.capture(trace_dir):
                    window = drive(tracing.annotation)
            else:
                window = drive(None)
        after = {name: snap(rt) for name, snap in snapshots.items()}
        reading = None
        if trace:
            names = set(tracing.HARNESS_ANNOTATIONS)
            names.update(s.name for s in served.tracer.spans())
            reading = tracing.reduce_trace(tracing.read_events(trace_dir),
                                           names)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Measured(window, sampler.kept, counter.count,
                    {name: (before[name], after[name]) for name in before},
                    reading)


def window_lines(window: loadgen.Window, compiles: int) -> List[str]:
    lag = np.asarray(window.lag_s) * 1e3
    lines = [
        f"window: {window.attempted} requests, {window.completed} completed, "
        f"{window.failed} failed, {window.batches} batches, "
        f"{window.seconds:.6f} s, {compiles} compiles",
        "generator lag (open loop, at each wake-up): " + (
            f"n {lag.size} p50 {np.percentile(lag, 50):.6f} ms "
            f"p95 {np.percentile(lag, 95):.6f} ms max {lag.max():.6f} ms"
            if lag.size else "none")]
    return lines + [f"error: {e}" for e in window.errors[:5]]


def run_cell(bench: Benchmark, cell_name: str, seed: int, seconds: float,
             trace: bool, devices, *, t_start: Optional[float] = None,
             sizes: Optional[dict] = None,
             peaks: Optional[dict] = None) -> dict:
    """Run ``cell_name`` once on ``devices`` and return the result line's
    object, with ``"stderr"``: the lines that go to standard error, the
    numbers compared last. ``sizes`` overrides the configuration's sizes
    (tests only)."""
    t_start = time.perf_counter() if t_start is None else t_start
    readers = {m.name: (m, bench.load_reader(m.name))
               for m in bench.metrics_for(cell_name, trace)}
    served = prepare(bench, cell_name, seed, trace, sizes=sizes)
    setup_s = time.perf_counter() - t_start
    m = measure(served, seconds, trace,
                snapshots={name: r.snapshot for name, (_, r) in
                           readers.items() if hasattr(r, "snapshot")})
    window = m.window
    mem = memory_peak_bytes(devices)

    run = Run(cell=cell_name, config=served.config, traffic=served.traffic,
              window=window, setup_s=setup_s, tracer=served.tracer,
              compiles=m.compiles, device=m.device, peaks=peaks)
    metrics = {}
    for name, (metric, reader) in readers.items():
        before, after = m.snapshots.get(name, (None, None))
        value = reader.read(dataclasses.replace(run, before=before,
                                                after=after))
        if value is not None:
            metrics[name] = {"value": value, "unit": metric.unit}

    # --- the check, once the program's state is freed
    config, program = served.config, served.program
    served_phases = served.setup_phases
    del run, served
    gc.collect()
    checks = check(config, program, m.kept)
    checks["lost_requests"] = {"value": window.attempted - window.completed,
                               "limit": 0}
    stderr = [f"setup: {setup_s:.3f} s; start (imports, devices) "
              f"{setup_s - sum(served_phases.values()):.3f} s, " + ", ".join(
                  f"{k} {v:.3f} s" for k, v in served_phases.items())]
    stderr += window_lines(window, m.compiles)
    stderr += [f"check {name}: {c['value']} "
               f"({'at least' if c.get('at_least') else 'limit'} "
               f"{c['limit']})" for name, c in checks.items()]
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": passes(checks),
           "attempted": window.attempted, "failed": window.failed,
           "metrics": metrics, "device": device}
    reading = m.device
    if reading is not None:
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in
                           reading.top(reading.program_s or reading.op_s)],
            "idle_gaps": [[k, v] for k, v in
                          reading.top(reading.idle_by_annotation)]}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    out["stderr"] = stderr
    return out
