"""Run one benchmark cell once, on the chips of this machine.

Usage::

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from the seed, registers its programs with a
``CobraSession`` and a ``ServingRuntime``, warms up, drives
``ServingRuntime.serve`` for the window, compares the served answers with
the configuration's plain reference, and prints one JSON object as the
last line of standard output: with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from the program's
spans and counters and from the profiler's device trace. The numbers
compared, each with its limit, are the last lines of standard error and
the last key of the result.

Exits 3, and prints no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.peaks import peaks_for
    from bench.registry import Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    try:
        devices = harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    peaks = peaks_for(devices[0].device_kind)
    print(f"bench: {args.workload} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache "
          f"{harness.enable_compile_cache()}", file=sys.stderr)
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices, t_start=T_START,
                           peaks=peaks)
    for line in out.pop("stderr"):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
