"""Operations and bytes that a kernel's work needs, from its shapes.

These are the algorithm's numbers, whatever implements it: a share of the
roofline compares them with the chip's peaks, so an implementation that
does more work than the algorithm needs reads as a lower share.
"""

from __future__ import annotations

KEY_BYTES = 4      # int32 keys and row indices
SLOT_BYTES = 4     # int32 direct-address slots


def join_probe_bytes(n_probe: int, n_slots: int) -> int:
    """One probe of ``n_probe`` keys against a direct-address table of
    ``n_slots`` slots: read each key, write each result row index, read
    each slot once."""
    return KEY_BYTES * n_probe + KEY_BYTES * n_probe + SLOT_BYTES * n_slots


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
