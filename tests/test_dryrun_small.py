"""Dry-run machinery on a small (8-device) mesh, via subprocess so the
XLA_FLAGS device-count override never leaks into this test session.

Validates:
  * lower+compile of train/decode steps on a 2×4 (data, model) mesh with
    fsdp_tp sharding for a reduced dense arch and a reduced MoE arch;
  * the two-point layer extrapolation against a fully-unrolled compile
    (exactness of the accounting methodology);
  * collective ops appear in the compiled HLO (the plan actually shards).
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # ~90s XLA compile fixture; excluded from test-fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax
import jax.numpy as jnp
from repro.models.arch import get_arch
from repro.launch.mesh import make_mesh
from repro.launch.sharding import make_policy
from repro.launch.specs import input_specs, make_optimizer, step_fn
from repro.analysis.roofline import collective_bytes_from_hlo
from repro.configs import SHAPES

# small shapes so compiles are fast
SHAPES["train_4k"] = dict(seq_len=128, global_batch=8, kind="train")
SHAPES["decode_32k"] = dict(seq_len=128, global_batch=8, kind="decode")

out = {}
mesh = make_mesh((2, 4), ("data", "model"))

def compile_cell(cfg, shape, kind, unroll):
    with mesh:
        pol = make_policy(mesh, strategy="fsdp_tp",
                          remat="full" if kind == "train" else "none",
                          microbatch=1, unroll_layers=unroll)
        opt = make_optimizer(cfg) if kind == "train" else None
        fn = step_fn(cfg, kind, pol, opt)
        args = input_specs(cfg, shape, pol, opt)
        compiled = jax.jit(fn).lower(*args.values()).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        coll = collective_bytes_from_hlo(compiled.as_text())
        return float(cost.get("flops", 0)), coll

for arch in ("h2o-danube-1.8b", "llama4-scout-17b-a16e"):
    base = get_arch(arch).scaled(n_layers=6, d_model=64, n_heads=4, d_ff=128,
                                 vocab=512)
    for shape, kind in (("train_4k", "train"), ("decode_32k", "decode")):
        f_full, coll = compile_cell(base, shape, kind, unroll=True)
        f2, _ = compile_cell(dataclasses.replace(base, n_layers=2), shape, kind, True)
        f4, _ = compile_cell(dataclasses.replace(base, n_layers=4), shape, kind, True)
        extrap = f2 + (6 - 2) * (f4 - f2) / 2
        out[f"{arch}/{shape}"] = {
            "flops_full": f_full, "flops_extrap": extrap,
            "rel_err": abs(extrap - f_full) / max(f_full, 1.0),
            "n_collectives": sum(coll["counts"].values()),
            "coll_types": sorted(coll["counts"]),
        }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    # a CPU dry-run: the child never reaches for the chip, which belongs
    # to one process at a time
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cells_compile_and_shard(results):
    for tag, r in results.items():
        assert r["n_collectives"] > 0, f"{tag}: no collectives — not sharded?"


def test_two_point_extrapolation_exact(results):
    """Layer stacks are homogeneous ⇒ linear extrapolation must match the
    fully-unrolled compile closely. Tolerance 6%: at this toy scale the
    non-layer intercept (loss/optimizer fusion differences between
    compiles) is proportionally larger than at full scale, where layers
    dominate by orders of magnitude."""
    for tag, r in results.items():
        abs_err = abs(r["flops_extrap"] - r["flops_full"])
        # decode cells at toy scale have ~2M total FLOPs — fusion noise in
        # the intercept dominates; accept small absolute error there
        assert r["rel_err"] < 0.06 or abs_err < 1e6, (tag, r)


def test_expected_collective_types(results):
    train = results["h2o-danube-1.8b/train_4k"]
    assert any(t in train["coll_types"] for t in ("all-reduce", "all-gather",
                                                  "reduce-scatter"))
