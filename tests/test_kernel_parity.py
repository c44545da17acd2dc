"""Golden parity: Pallas kernels (interpret mode) vs the numpy reference
path (``kernels/ref.py``) the "numpy" compiled backend executes.

The compiled execution tier promises bit-identical results whichever
backend serves a columnar loop, so the kernels themselves must agree with
their numpy twins on exactly the shapes real plans produce: empty probe and
build sides, all-miss key sets, group counts above one tile, and skewed
segment sizes. Run with ``JAX_PLATFORMS=cpu`` in CI.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import build_direct_table, join_probe, segment_reduce  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

RNG = np.random.default_rng(11)


def direct(table_keys, key_space):
    return build_direct_table(jnp.asarray(table_keys, jnp.int32), key_space)


# --------------------------------------------------------------------------
# join_probe: Pallas kernel vs numpy twin
# --------------------------------------------------------------------------

class TestJoinProbeParity:
    def check(self, probe, build, key_space):
        probe = np.asarray(probe, np.int32)
        build = np.asarray(build, np.int32)
        got = np.asarray(join_probe(jnp.asarray(probe),
                                    direct(build, key_space),
                                    interpret=True))
        want = ref.join_probe_np(probe, build)
        np.testing.assert_array_equal(got, want)
        # and the jnp reference agrees with its numpy twin
        np.testing.assert_array_equal(
            np.asarray(ref.join_probe_ref(jnp.asarray(probe),
                                          jnp.asarray(build))), want)

    def test_empty_probe_side(self):
        self.check([], [3, 1, 4], 8)

    def test_empty_build_side(self):
        probe = np.asarray([0, 1, 2], np.int32)
        got = np.asarray(join_probe(jnp.asarray(probe),
                                    jnp.zeros((0,), jnp.int32),
                                    interpret=True))
        np.testing.assert_array_equal(got,
                                      ref.join_probe_np(probe, np.zeros(0)))
        assert (got == -1).all()

    def test_all_miss_keys(self):
        self.check([100, 200, 300, 7], [1, 2, 3], 512)

    def test_duplicate_probe_keys(self):
        self.check([2, 2, 5, 2, 5, 9], [9, 5, 2], 16)

    def test_random_sweep_past_one_block(self):
        build = RNG.permutation(4096)[:1500].astype(np.int32)
        probe = RNG.integers(0, 4096, size=3000).astype(np.int32)
        probe_j = jnp.asarray(probe)
        want = ref.join_probe_np(probe, build)
        got = np.asarray(join_probe(probe_j, direct(build, 4096),
                                    block_n=256, interpret=True))
        np.testing.assert_array_equal(got, want)
        hit = want >= 0
        assert hit.any() and (~hit).any()     # the sweep exercises both
        np.testing.assert_array_equal(build[want[hit]], probe[hit])


# --------------------------------------------------------------------------
# segment_reduce: Pallas kernel vs numpy twin
# --------------------------------------------------------------------------

class TestSegmentReduceParity:
    def check(self, values, segs, n_groups, op="sum", **kw):
        values = np.asarray(values, np.float32)
        segs = np.asarray(segs, np.int32)
        got = np.asarray(segment_reduce(jnp.asarray(values),
                                        jnp.asarray(segs), n_groups, op=op,
                                        interpret=True, **kw))
        want = ref.segment_reduce_np(values, segs, n_groups, op=op)
        np.testing.assert_allclose(got, want, rtol=0, atol=0)
        # the jnp oracle keeps jax's +-inf identity for empty min/max
        # groups; the kernel and its numpy twin map those to 0
        oracle = np.asarray(ref.segment_reduce_ref(jnp.asarray(values),
                                                   jnp.asarray(segs),
                                                   n_groups, op=op))
        oracle = np.where(np.isfinite(oracle), oracle, 0.0)
        np.testing.assert_allclose(oracle, want, rtol=0, atol=0)

    def test_empty_input(self):
        self.check([], [], 4)

    def test_zero_groups(self):
        self.check([], [], 0)

    def test_groups_above_one_tile(self):
        # 30 groups through a 8-wide group tile: 4 grid steps over groups
        segs = RNG.integers(0, 30, size=500)
        vals = RNG.integers(0, 9, size=500)
        self.check(vals, segs, 30, block_g=8, block_n=64)

    def test_skewed_segments(self):
        # one giant segment, several empty ones
        segs = np.zeros(1000, np.int32)
        segs[:3] = [7, 7, 3]
        vals = np.ones(1000)
        self.check(vals, segs, 8, block_n=128)

    @pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
    def test_ops_with_empty_groups(self, op):
        segs = [0, 0, 2, 2, 2]          # group 1 and 3 empty
        vals = [3.0, -1.0, 5.0, 2.0, 2.0]
        self.check(vals, segs, 4, op=op, block_n=4, block_g=2)


# --------------------------------------------------------------------------
# ops dispatch: Pallas on/off must be value-identical
# --------------------------------------------------------------------------

class TestOpsDispatch:
    def test_equi_probe_pallas_toggle(self):
        probe = jnp.asarray(RNG.integers(0, 64, size=200), jnp.int32)
        build = jnp.asarray(RNG.permutation(64)[:40], jnp.int32)
        state = ops.pallas_state()
        try:
            ops.use_pallas(False)
            off = np.asarray(ops.equi_probe(probe, build, key_space=64))
            ops.use_pallas(True, interpret=True)
            on = np.asarray(ops.equi_probe(probe, build, key_space=64))
        finally:
            ops.use_pallas(state[0], interpret=state[1])
        np.testing.assert_array_equal(off, on)
        np.testing.assert_array_equal(
            off, ref.join_probe_np(np.asarray(probe), np.asarray(build)))

    def test_segment_reduce_pallas_toggle(self):
        vals = jnp.asarray(RNG.integers(0, 5, size=300), jnp.float32)
        segs = jnp.asarray(RNG.integers(0, 10, size=300), jnp.int32)
        state = ops.pallas_state()
        try:
            ops.use_pallas(False)
            off = np.asarray(ops.segment_reduce(vals, segs, 10))
            ops.use_pallas(True, interpret=True)
            on = np.asarray(ops.segment_reduce(vals, segs, 10))
        finally:
            ops.use_pallas(state[0], interpret=state[1])
        np.testing.assert_allclose(off, on, rtol=0, atol=0)

    def test_equi_probe_without_key_space_uses_ref(self):
        # no key_space -> no direct table; must still match the numpy twin
        probe = np.asarray([5, 1, 99, 1], np.int32)
        build = np.asarray([1, 5, 7], np.int32)
        got = np.asarray(ops.equi_probe(jnp.asarray(probe),
                                        jnp.asarray(build)))
        np.testing.assert_array_equal(got, ref.join_probe_np(probe, build))
