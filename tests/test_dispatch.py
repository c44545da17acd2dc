"""Device selection: kernel dispatch by platform, the probe's admission
rule, and the compile-cache directory of the entry points.

These run on the CPU. Where a test needs the TPU's branch it passes the
platform in (``jax.default_backend`` patched for the test), and the
kernels are replaced by recorders, so nothing is compiled for a chip.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile_cache import CHECKOUT_CACHE, compile_cache_dir
from repro.kernels import ops

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("platform,impl", [
    ("tpu", ops.PALLAS), ("cpu", ops.REF), ("gpu", ops.REF)])
def test_dispatch_rule_by_platform(platform, impl):
    assert ops.impl_for(platform) == impl


@pytest.fixture
def recorders(monkeypatch):
    """Replace both kernels with recorders of their ``interpret`` flag."""
    seen = []

    def segred(values, segment_ids, num_segments, op, interpret):
        seen.append(("segment_reduce", interpret))
        return np.zeros((num_segments,), np.float32)

    def probe(keys, table, interpret):
        seen.append(("join_probe", interpret))
        return np.zeros(keys.shape, np.int32)

    monkeypatch.setattr(ops, "_segred_pallas", segred)
    monkeypatch.setattr(ops, "_probe_pallas", probe)
    return seen


def on_platform(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)


def test_tpu_runs_compiled_kernels(monkeypatch, recorders):
    vals, segs = jnp.ones(8), jnp.zeros(8, jnp.int32)
    keys, build = jnp.arange(8, dtype=jnp.int32), jnp.arange(4,
                                                             dtype=jnp.int32)
    on_platform(monkeypatch, "tpu")
    assert ops.impl() == ops.PALLAS
    ops.segment_reduce(vals, segs, 1)
    ops.equi_probe(keys, build, key_space=4)
    assert recorders == [("segment_reduce", False), ("join_probe", False)]


def test_cpu_runs_references(monkeypatch, recorders):
    on_platform(monkeypatch, "cpu")
    assert ops.impl() == ops.REF
    out = ops.segment_reduce(jnp.ones(8), jnp.zeros(8, jnp.int32), 1)
    assert float(out[0]) == 8.0
    got = ops.equi_probe(jnp.asarray([3, 9], jnp.int32),
                         jnp.asarray([1, 3], jnp.int32), key_space=4)
    np.testing.assert_array_equal(np.asarray(got), [1, -1])
    assert recorders == []


@pytest.mark.parametrize("key_space", [None, 0, ops.MAX_KEY_SPACE + 1])
def test_probe_outside_the_rule_takes_the_reference(monkeypatch, recorders,
                                                    key_space):
    on_platform(monkeypatch, "tpu")
    assert ops.probe_impl(key_space) == ops.REF
    got = ops.equi_probe(jnp.asarray([3, 9], jnp.int32),
                         jnp.asarray([1, 3], jnp.int32), key_space=key_space)
    np.testing.assert_array_equal(np.asarray(got), [1, -1])
    assert recorders == []


def test_test_override_forces_interpret_mode(monkeypatch):
    on_platform(monkeypatch, "tpu")
    state = ops.pallas_state()
    try:
        ops.use_pallas(True, interpret=True)
        assert ops.impl() == ops.INTERPRET
        ops.use_pallas(False)
        assert ops.impl() == ops.REF
    finally:
        ops.use_pallas(*state)
    assert ops.impl() == ops.PALLAS


@pytest.mark.parametrize("keys,space", [
    ([0, 3, 7], 8),
    ([5], 6),
    ([], None),                             # nothing to build a table from
    ([-1, 2], None),                        # negative key
    ([1, 2, 2], None),                      # duplicate key
    ([0, ops.MAX_KEY_SPACE], None),         # past the admitted key space
    ([0.0, 1.0], None),                     # not integers
])
def test_direct_key_space_rule(keys, space):
    dtype = np.float64 if any(isinstance(k, float) for k in keys) \
        else np.int64
    assert ops.direct_key_space(np.asarray(keys, dtype)) == space


def test_compile_cache_dir_from_environment():
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x/c"}) == "/x/c"


def test_compile_cache_dir_defaults_to_checkout_root():
    assert compile_cache_dir({}) == str(REPO / ".jax_cache")
    assert CHECKOUT_CACHE.parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
