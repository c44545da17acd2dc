"""Benchmark harness — one module per paper table/figure.

  exp_crossover  Fig. 13 a/b/c  (P0/P1/P2 crossover + Cobra's choice)
  exp_wilos      Fig. 14/15     (Wilos patterns A–F, 4 bars each)
  exp_opt_time   Sec. VIII      (optimization time < 1 s + plan-cache hit)
  bench_runtime  serving runtime: batch-size/throughput crossover +
                 plan-store warm start (beyond-paper)

Usage::

    python -m benchmarks.run [--smoke] [module ...]

``--smoke`` sets ``REPRO_BENCH_SMOKE=1`` before importing the drivers,
shrinking every workload to a seconds-long configuration — the CI guard
against API drift in the benchmark drivers (``make bench-smoke``). With no
module arguments all modules run.

Prints ``name,us_per_call,derived`` CSV. A module whose ``main(emit)``
returns a dict additionally gets that trajectory written to
``BENCH_<module>.json`` (e.g. ``BENCH_runtime.json`` with throughput at
batch sizes 1/8/64).
"""

import json
import os
import sys
import time


def emit(name, value, derived=""):
    print(f"{name},{value},{derived}", flush=True)


def main() -> None:
    args = sys.argv[1:]
    if "--smoke" in args:
        args.remove("--smoke")
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import bench_runtime, exp_crossover, exp_opt_time, exp_wilos
    mods = {"exp_crossover": exp_crossover, "exp_wilos": exp_wilos,
            "exp_opt_time": exp_opt_time, "bench_runtime": bench_runtime}
    unknown = [a for a in args if a not in mods]
    if unknown:
        print(f"unknown module(s) {unknown}; available: {sorted(mods)}",
              file=sys.stderr)
        sys.exit(2)
    selected = args or list(mods)
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        mod = mods[name]
        t0 = time.time()
        try:
            trajectory = mod.main(emit)
            emit(f"{name}/__total_s", (time.time() - t0) * 1e6, "harness")
            if isinstance(trajectory, dict):
                out = f"BENCH_{name.replace('bench_', '')}.json"
                with open(out, "w") as f:
                    json.dump(trajectory, f, indent=1, sort_keys=True)
                emit(f"{name}/__trajectory", 0, out)
        except Exception as e:  # keep the harness going
            failures += 1
            emit(f"{name}/__error", 0, repr(e)[:120])
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
