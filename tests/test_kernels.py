"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import build_direct_table, join_probe, segment_reduce
from repro.kernels import ref

KEY = jax.random.PRNGKey(7)


def keys(n):
    return jax.random.split(KEY, n)


# --------------------------------------------------------------------------
# segment reduce (relational γ)
# --------------------------------------------------------------------------

SEG_SWEEP = [
    (100, 7, "sum"), (512, 64, "sum"), (1000, 13, "count"),
    (257, 5, "min"), (300, 999, "max"), (64, 1, "sum"),
]


@pytest.mark.parametrize("N,G,op", SEG_SWEEP)
def test_segment_reduce_matches_ref(N, G, op):
    k1, k2 = keys(2)
    vals = jax.random.normal(k1, (N,), jnp.float32)
    segs = jax.random.randint(k2, (N,), 0, G)
    got = segment_reduce(vals, segs, G, op=op, block_n=64, block_g=128,
                         interpret=True)
    want = ref.segment_reduce_ref(vals, segs, G, op=op)
    # empty groups: kernel emits 0 for min/max; align oracle
    if op in ("min", "max"):
        counts = ref.segment_reduce_ref(jnp.ones_like(vals), segs, G, "sum")
        want = jnp.where(counts > 0, want, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# join probe
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_build,n_probe,space", [
    (50, 200, 64), (1000, 333, 1024), (7, 1500, 8),
])
def test_join_probe_matches_ref(n_build, n_probe, space):
    rng = np.random.default_rng(0)
    build = jnp.asarray(rng.choice(space, size=n_build, replace=False)
                        .astype(np.int32))
    probe = jnp.asarray(rng.integers(0, space, n_probe).astype(np.int32))
    table = build_direct_table(build, space)
    got = join_probe(probe, table, block_n=128, interpret=True)
    want = ref.join_probe_ref(probe, build)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_join_probe_roundtrip_semantics():
    """probe→gather reproduces the relational equi-join."""
    rng = np.random.default_rng(1)
    build = jnp.asarray(np.arange(100, dtype=np.int32))
    payload = jnp.asarray(rng.normal(size=100).astype(np.float32))
    probe = jnp.asarray(rng.integers(0, 100, 400).astype(np.int32))
    table = build_direct_table(build, 128)
    idx = join_probe(probe, table, interpret=True)
    joined = payload[idx]
    np.testing.assert_allclose(np.asarray(joined),
                               np.asarray(payload)[np.asarray(probe)])
