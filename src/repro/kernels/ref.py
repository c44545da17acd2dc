"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Also hosts the numpy twins (``*_np``) that the compiled execution tier's
``"numpy"`` backend runs when it is requested by name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def segment_reduce_ref(values, segment_ids, num_segments: int, op: str = "sum"):
    """Relational γ oracle: per-group sum/count/min/max."""
    if op == "sum":
        return jax.ops.segment_sum(values, segment_ids, num_segments)
    if op == "count":
        return jax.ops.segment_sum(jnp.ones_like(values), segment_ids,
                                   num_segments)
    if op == "min":
        return jax.ops.segment_min(values, segment_ids, num_segments)
    if op == "max":
        return jax.ops.segment_max(values, segment_ids, num_segments)
    raise ValueError(op)


def join_probe_ref(probe_keys, table_keys):
    """For each probe key: index of its match in table_keys (unique) or -1."""
    n = probe_keys.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    if table_keys.shape[0] == 0:
        return jnp.full((n,), -1, jnp.int32)
    order = jnp.argsort(table_keys)
    sk = table_keys[order]
    pos = jnp.clip(jnp.searchsorted(sk, probe_keys), 0, len(order) - 1)
    idx = order[pos]
    found = table_keys[idx] == probe_keys
    return jnp.where(found, idx, -1).astype(jnp.int32)


def join_probe_np(probe_keys, table_keys):
    """numpy twin of :func:`join_probe_ref` (the ``"numpy"`` compiled
    backend)."""
    probe_keys = np.asarray(probe_keys)
    table_keys = np.asarray(table_keys)
    n = probe_keys.shape[0]
    if n == 0:
        return np.zeros((0,), np.int32)
    if table_keys.shape[0] == 0:
        return np.full((n,), -1, np.int32)
    order = np.argsort(table_keys, kind="stable")
    sk = table_keys[order]
    pos = np.clip(np.searchsorted(sk, probe_keys), 0, len(order) - 1)
    idx = order[pos]
    found = table_keys[idx] == probe_keys
    return np.where(found, idx, -1).astype(np.int32)


def segment_reduce_np(values, segment_ids, num_segments: int, op: str = "sum"):
    """numpy twin of :func:`segment_reduce_ref`, with the Pallas kernel's
    empty-group convention for min/max (empty groups report 0)."""
    values = np.asarray(values, np.float32)
    segment_ids = np.asarray(segment_ids)
    if op == "count":
        values = np.ones_like(values)
        op = "sum"
    if op == "sum":
        out = np.zeros((num_segments,), np.float32)
        np.add.at(out, segment_ids, values)
        return out
    if op == "min":
        out = np.full((num_segments,), np.inf, np.float32)
        np.minimum.at(out, segment_ids, values)
    elif op == "max":
        out = np.full((num_segments,), -np.inf, np.float32)
        np.maximum.at(out, segment_ids, values)
    else:
        raise ValueError(op)
    return np.where(np.isfinite(out), out, 0.0).astype(np.float32)
