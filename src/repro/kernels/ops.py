"""Kernel dispatch: the compiled Pallas kernels on the TPU, the jnp
references elsewhere.

The platform decides (:func:`impl_for`): on ``tpu`` the relational kernels
run compiled (``interpret=False``); on any other platform the jnp
references in ``ref.py`` run. Pallas interpret mode runs only when a test
asks for it with ``use_pallas(True, interpret=True)``.

A shape a kernel cannot take goes to the reference by a stated rule
(:func:`direct_key_space` for the probe) and is reported as ``"ref"``, so
callers that count kernel calls count what actually ran.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from . import ref
from .join_probe import build_direct_table, join_probe as _probe_pallas
from .segment_reduce import segment_reduce as _segred_pallas

PALLAS, INTERPRET, REF = "pallas", "interpret", "ref"

# largest direct-address table the probe kernel is given: 16 MiB of slots,
# 24 MiB of bf16 byte planes in HBM, tiled through VMEM by the kernel
MAX_KEY_SPACE = 1 << 22

# test override: None = the platform decides
_FORCED = {"use_pallas": None, "interpret": False}


def use_pallas(on: Optional[bool], interpret: bool = False) -> None:
    """Force the Pallas kernels on (``True``) or off (``False``) — tests use
    this to run the kernels in interpret mode on the CPU. ``None`` hands
    the choice back to the platform."""
    _FORCED["use_pallas"] = on
    _FORCED["interpret"] = interpret


def pallas_state() -> tuple:
    """The override as ``(use_pallas, interpret)``; restore it with
    ``use_pallas(*state)``."""
    return (_FORCED["use_pallas"], _FORCED["interpret"])


def impl_for(platform: str) -> str:
    """The dispatch rule: compiled Pallas on ``tpu``, the reference
    elsewhere."""
    return PALLAS if platform == "tpu" else REF


def impl() -> str:
    """The implementation the relational kernels take in this process."""
    on = _FORCED["use_pallas"]
    if on is None:
        return impl_for(jax.default_backend())
    if not on:
        return REF
    return INTERPRET if _FORCED["interpret"] else PALLAS


def direct_key_space(sorted_keys: np.ndarray) -> Optional[int]:
    """Key space of the direct-address table for these build keys (given
    sorted), or None when the probe kernel cannot take them: keys that are
    not integers, negative, not unique, or span more than
    :data:`MAX_KEY_SPACE` slots."""
    if sorted_keys.size == 0 \
            or not np.issubdtype(sorted_keys.dtype, np.integer):
        return None
    lo, hi = int(sorted_keys[0]), int(sorted_keys[-1])
    if lo < 0 or hi >= MAX_KEY_SPACE \
            or (sorted_keys.size > 1 and not np.all(np.diff(sorted_keys))):
        return None
    return hi + 1


def segment_reduce(values, segment_ids, num_segments: int, op: str = "sum"):
    how = impl()
    if how != REF:
        return _segred_pallas(values, segment_ids, num_segments, op=op,
                              interpret=how == INTERPRET)
    return ref.segment_reduce_ref(values, segment_ids, num_segments, op=op)


def probe_impl(key_space: Optional[int]) -> str:
    """What :func:`equi_probe` runs for a build side of ``key_space``
    slots: the kernel's implementation when the key space is known and at
    most :data:`MAX_KEY_SPACE`, the reference otherwise."""
    if key_space is None or not 0 < key_space <= MAX_KEY_SPACE:
        return REF
    return impl()


def equi_probe(probe_keys, table_keys, key_space: Optional[int] = None):
    """Index of each probe key's match in table_keys (-1 if absent)."""
    how = probe_impl(key_space)
    if how != REF:
        table = build_direct_table(table_keys, key_space)
        return _probe_pallas(probe_keys, table, interpret=how == INTERPRET)
    return ref.join_probe_ref(probe_keys, table_keys)
