"""What tracing costs a cell: its served rate with the program's spans and
the profiler each on and off, in one process.

Usage::

    python bench/tracing_cost.py --workload <cell> --seed <n> --seconds <s> \\
        --rounds 2

Builds and warms the cell once with the recording tracer the traced runs
use (``bench.tracing.AnnotatingTracer``), then serves its mix for one
window per mode and round, the modes in turn (rotated each round):
``none`` (the no-op tracer every untraced run has, no profiler),
``spans`` (the recording tracer, no profiler), ``profiler`` (the no-op
tracer under the profiler) and ``both`` (a ``--trace 1`` run). Prints one
JSON line per window: mode, round, served rate, p50 latency, requests
completed and spans recorded. A process speeds up over its first windows,
so compare modes within a round.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
MODES = ("none", "spans", "profiler", "both")


def set_tracer(served, tracer) -> None:
    """Every holder of the program's tracer: the session (batches,
    compiles), the runtime (serve, feedback) and the server."""
    rt = served.rt
    rt.session.tracer = rt.tracer = rt.session.db.tracer = tracer


def measure_modes(served, seconds: float, rounds: int, modes=MODES):
    """One window per mode and round; yields one result dict a window."""
    import numpy as np
    from bench import harness
    from repro.obs.trace import NOOP_TRACER

    tracer = served.tracer
    try:
        for r in range(rounds):
            k = r % len(modes)
            for mode in modes[k:] + modes[:k]:
                tracer.reset()
                spans = mode in ("spans", "both")
                set_tracer(served, tracer if spans else NOOP_TRACER)
                m = harness.measure(served, seconds,
                                    mode in ("profiler", "both"))
                w = m.window
                lat = np.asarray(w.latencies_s()) * 1e3
                yield {"mode": mode, "round": r,
                       "served_rps": w.completed / w.seconds,
                       "p50_ms": float(np.percentile(lat, 50)),
                       "completed": w.completed, "failed": w.failed,
                       "compiles": m.compiles,
                       "spans": len(tracer.spans()) if spans else 0}
    finally:
        set_tracer(served, tracer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.registry import Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    try:
        harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"tracing_cost: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    served = harness.prepare(bench, args.workload, args.seed, True)
    for line in measure_modes(served, args.seconds, args.rounds):
        print(json.dumps(dict(line, workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
