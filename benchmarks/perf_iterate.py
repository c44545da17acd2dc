"""§Perf hillclimbing harness: hypothesis → change → re-lower → measure.

Run standalone (it forks a 512-device subprocess per variant so the XLA
device flag never leaks):

    PYTHONPATH=src python -m benchmarks.perf_iterate --pair kimi_train

Each pair has a list of (variant name, hypothesis, policy change). Results
land in reports/perf/<pair>.json: before/after roofline terms per variant,
confirmed/refuted per the recorded hypothesis.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# (arch, shape) → list of variants: (name, hypothesis, kwargs for run_cell)
# The three hillclimbed pairs (§Perf):
#   kimi_train   — most collective-bound (869s ICI) AND most representative
#                  of the paper's technique: the FSDP-regather-vs-own
#                  decision is Cobra's N1 (prefetch/cache) analogue, and the
#                  expert dispatch is T4 (batch lookups into a join).
#   llama4_train — worst roofline fraction among train cells (0.012,
#                  memory-dominated MoE dispatch traffic).
#   rwkv_decode  — the only collective-dominant decode cell (weight
#                  regathers sit on a tiny-compute critical path).
PAIRS = {
    "kimi_train": ("kimi-k2-1t-a32b", "train_4k", [
        ("baseline_fsdp_tp",
         "baseline: FSDP regathers 8.5GB of expert weights per MoE layer "
         "per direction → collective-bound (measured 869s)", {}),
        ("ep_owned",
         "napkin: per layer, regather moves E/16·3·d·ff_moe·2B ≈ 8.5GB "
         "but the (E/16,C,d) activation buffer is only ≈ 0.6GB → owning "
         "experts (E on model × ffn on data) and reducing activations "
         "instead should cut the collective term ≈ 10×",
         {"strategy": "fsdp_tp_ep"}),
        ("ep_remat_dots",
         "with collectives down, remat=full recompute traffic may bound; "
         "dots-policy remat re-reads less",
         {"strategy": "fsdp_tp_ep", "remat": "dots"}),
        ("ep_remat_none",
         "remat off entirely: compute floor; memory_analysis tells whether "
         "activations still fit at mb=8",
         {"strategy": "fsdp_tp_ep", "remat": "none"}),
    ]),
    "llama4_train": ("llama4-scout-17b-a16e", "train_4k", [
        ("baseline_fsdp_tp",
         "baseline: memory term 176s — scatter/gather dispatch traffic "
         "plus remat=full re-reads dominate", {}),
        ("ep_owned",
         "same EP ownership as kimi: kill the per-layer expert regather "
         "(16e × 3·5120·8192·2B ≈ 1.3GB/layer/dir)",
         {"strategy": "fsdp_tp_ep"}),
        ("ep_remat_dots",
         "dots remat: recompute only matmuls, halve activation re-reads",
         {"strategy": "fsdp_tp_ep", "remat": "dots"}),
        ("ep_mb4",
         "fewer microbatches → fewer dispatch scatter passes over HBM per "
         "step at larger per-pass buffers",
         {"strategy": "fsdp_tp_ep", "microbatch": 4}),
    ]),
    # BONUS pair (beyond the required three): the planner's analytic model
    # predicts pure FSDP beats fsdp_tp for a 12B dense model at TP=16
    # (per-layer activation all-reduces cost more than the spread-out
    # regather) — test that prediction against the compiled artifact.
    "stablelm_train": ("stablelm-12b", "train_4k", [
        ("baseline_fsdp_tp",
         "baseline: TP(16) pays 4 all-reduces/layer of B_loc·T·d bytes", {}),
        ("fsdp_only",
         "planner prediction: drop TP — no per-layer activation "
         "all-reduces; 12B × 10B/param / 256 chips ≈ 0.5GB/chip resident",
         {"strategy": "fsdp"}),
        ("tp_only",
         "counter-hypothesis: TP keeps weights resident (1.5GB/chip), "
         "trades regather for activation all-reduces", {"strategy": "tp"}),
    ]),
    "rwkv_decode": ("rwkv6-3b", "decode_32k", [
        ("baseline_fsdp_tp",
         "baseline: collective 15.0ms > memory 4.9ms — per-step FSDP "
         "weight gathers sit on the decode critical path", {}),
        ("tp_only",
         "N1 analogue (gather once = keep resident): TP shards 3B params "
         "to 375MB/chip, removing the per-step regather → collective term "
         "should drop to activation all-reduces only", {"strategy": "tp"}),
        ("dp_replicated",
         "B=128 decode: replicate all weights (6GB, fits) → zero weight "
         "collectives; memory term becomes the pure floor",
         {"strategy": "dp"}),
    ]),
}

_RUNNER = r"""
import json, sys
from repro.launch.dryrun import run_cell   # sets XLA_FLAGS on import
spec = json.loads(sys.argv[1])
rec = run_cell(spec["arch"], spec["shape"], multi_pod=False,
               verbose=False, **spec["kwargs"])
slim = {k: rec[k] for k in ("roofline", "full_compile", "policy",
                            "flops_per_device", "bytes_per_device")
        if k in rec}
slim["collective_bytes_per_device"] = rec["collectives"]["bytes_per_device"]
print("@@RESULT@@" + json.dumps(slim))
"""


def run_variant(arch, shape, kwargs):
    # a CPU dry-run: the child never reaches for the chip, which belongs
    # to one process at a time
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    spec = json.dumps({"arch": arch, "shape": shape, "kwargs": kwargs})
    proc = subprocess.run([sys.executable, "-c", _RUNNER, spec], env=env,
                          capture_output=True, text=True, timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith("@@RESULT@@"):
            return json.loads(line[len("@@RESULT@@"):])
    raise RuntimeError(proc.stderr[-2000:])


def run_pair(pair: str, out_dir: str = "reports/perf"):
    arch, shape, variants = PAIRS[pair]
    os.makedirs(out_dir, exist_ok=True)
    results = []
    baseline_terms = None
    for name, hypothesis, kwargs in variants:
        print(f"[{pair}] {name} ...", flush=True)
        try:
            rec = run_variant(arch, shape, kwargs)
        except Exception as e:
            results.append({"variant": name, "hypothesis": hypothesis,
                            "status": "error", "error": repr(e)[:300]})
            continue
        rf = rec["roofline"]
        row = {"variant": name, "hypothesis": hypothesis, "status": "ok",
               "terms": {k: rf[k] for k in ("compute_s", "memory_s",
                                            "collective_s")},
               "dominant": rf["dominant"],
               "roofline_fraction": rf["roofline_fraction"],
               "fraction_vs_collective": rf.get("fraction_vs_collective"),
               "policy": rec["policy"]}
        if baseline_terms is None:
            baseline_terms = row["terms"]
            row["verdict"] = "baseline"
        else:
            dom0 = max(baseline_terms, key=baseline_terms.get)
            delta = (baseline_terms[dom0] - row["terms"][dom0]) \
                / max(baseline_terms[dom0], 1e-12)
            row["delta_on_baseline_dominant"] = delta
            row["verdict"] = "confirmed" if delta > 0.05 else (
                "neutral" if abs(delta) <= 0.05 else "refuted")
        results.append(row)
        print(f"    {row.get('verdict')} dom={row['dominant']} "
              f"frac={row['roofline_fraction']:.4f}", flush=True)
    path = os.path.join(out_dir, f"{pair}.json")
    with open(path, "w") as f:
        json.dump({"pair": pair, "arch": arch, "shape": shape,
                   "iterations": results}, f, indent=1)
    print(f"wrote {path}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default=None, choices=list(PAIRS) + [None])
    args = ap.parse_args()
    for pair in ([args.pair] if args.pair else list(PAIRS)):
        run_pair(pair)


if __name__ == "__main__":
    main()
