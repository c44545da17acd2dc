"""The control of a cell's check: the plain reference in the program's
place, computed in the precision below the one the configuration states.

Usage::

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, in one process: build and warm the cell, serve one window
at the cell's own load, then compute the check's numbers twice on the same
requests: once for the program's answers (the lower reading) and once for
the control's answers (``control`` of the configuration's reference
module: bfloat16 arithmetic), which have to fail the limits. Prints one
JSON line per seed. The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_checks(config, program: str, kept) -> dict:
    """The check's numbers with the control's answers in the program's
    place, for the requests in ``kept``."""
    from bench.harness import check
    ref = config.reference
    return check(config, program, kept,
                 answer=lambda params, outputs: ref.control(
                     config.columns, program, params))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.registry import Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    try:
        harness.require_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    for seed in args.seeds:
        served = harness.prepare(bench, args.workload, seed, False)
        m = harness.measure(served, args.seconds, False)
        config, program = served.config, served.program
        del served
        gc.collect()
        print(json.dumps({
            "seed": seed, "attempted": m.window.attempted,
            "program": harness.check(config, program, m.kept),
            "control": control_checks(config, program, m.kept)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
