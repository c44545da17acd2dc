"""Sweep the offered rate of a cell's mix in an open loop, to find its
knee.

Usage::

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 5 10 20 40

Builds and warms the cell once, then serves its mix as an open loop (a
closed loop's mix too) for one window per rate, in one process, and
prints one JSON line per rate: offered and served rate,
p50/p95 latency, how long the queue took to drain after the last arrival,
p95 latency of the window's first and second half of requests (a backlog
that grows shows as a second half slower than the first), and how late
the generator ran. The knee is the highest rate served without a growing
backlog; the cell's traffic file then carries 0.8 of it as a number.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np
    from bench import harness
    from bench.registry import Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    try:
        harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    served = harness.prepare(bench, args.workload, args.seed, False)
    for k, rate in enumerate(args.rates):
        traffic = dict(served.traffic, loop="open", rate_rps=rate,
                       check_sample=0)
        m = harness.measure(served, args.seconds, False, traffic=traffic,
                            stream=100 + k)
        w = m.window
        lat = np.asarray(w.latencies_s()) * 1e3
        half = len(lat) // 2
        lag = np.asarray(w.lag_s) * 1e3
        print(json.dumps({
            "rate_rps": rate,
            "served_rps": w.completed / w.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
            "drain_s": w.t_end - (w.t0 + args.seconds),
            "lag_p95_ms": float(np.percentile(lag, 95)) if lag.size else None,
            "failed": w.failed, "compiles": m.compiles,
            "batches": w.batches}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
