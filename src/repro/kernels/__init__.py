"""Pallas TPU kernels for the relational operators' hot spots.

  segment_reduce  — relational γ group-by aggregation (masked VPU fold)
  join_probe      — direct-address equi-join probe (application-side join)

Each kernel has a pure-jnp oracle in ``ref.py``. ``ops.py`` dispatches the
kernels by platform: compiled Pallas on the TPU, the jnp reference
elsewhere. Tests run the kernels in interpret mode on the CPU by passing
``interpret=True`` (tests/test_kernels.py), and compile them for a
described TPU v5e (tests/test_tpu_compile.py).
"""

from . import ops, ref
from .join_probe import build_direct_table, join_probe
from .segment_reduce import segment_reduce

__all__ = ["ops", "ref", "segment_reduce", "join_probe", "build_direct_table"]
