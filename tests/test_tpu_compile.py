"""The relational kernels compile for a TPU v5e, at main-path widths.

No chip is needed: the TPU compiler describes a v5e topology and compiles
for it (``interpret=False``), which refuses what the chip would refuse —
unaligned blocks, gathers Mosaic cannot lower, too much VMEM. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.join_probe import join_probe
from repro.kernels.segment_reduce import segment_reduce

N_ROWS = 1_000_000          # orders at the paper's Experiment-1 scale
N_CUSTOMERS = 73_000        # its customer table: the direct table's slots


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("key_space", [N_CUSTOMERS, ops.MAX_KEY_SPACE],
                         ids=["customers", "max_key_space"])
def test_join_probe_compiles(one_chip, no_persistent_cache, key_space):
    compile_for(one_chip,
                lambda keys, table: join_probe(keys, table, interpret=False),
                ((N_ROWS,), jnp.int32), ((key_space,), jnp.int32))


def test_segment_reduce_one_segment_compiles(one_chip, no_persistent_cache):
    # the compiled tier's scalar fold (_fold_sum)
    compile_for(one_chip,
                lambda v, s: segment_reduce(v, s, 1, op="sum",
                                            interpret=False),
                ((N_ROWS,), jnp.float32), ((N_ROWS,), jnp.int32))


@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_reduce_grouped_compiles(one_chip, no_persistent_cache, op):
    compile_for(one_chip,
                lambda v, s: segment_reduce(v, s, 4096, op=op,
                                            interpret=False),
                ((N_ROWS,), jnp.float32), ((N_ROWS,), jnp.int32))
