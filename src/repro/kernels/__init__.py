"""Pallas TPU kernels for the framework's compute hot spots.

  flash_attention — blocked online-softmax attention (causal/SWA/chunked/GQA)
  rwkv6_scan      — chunked WKV linear-attention scan (data-dependent decay)
  segment_reduce  — relational γ group-by aggregation (masked VPU fold)
  join_probe      — direct-address equi-join probe (application-side join)

Each kernel has a pure-jnp oracle in ``ref.py``. ``ops.py`` dispatches the
relational kernels by platform: compiled Pallas on the TPU, the jnp
reference elsewhere. Tests run the kernels in interpret mode on the CPU by
passing ``interpret=True`` (tests/test_kernels.py), and compile them for a
described TPU v5e (tests/test_tpu_compile.py).
"""

from . import ops, ref
from .flash_attention import flash_attention
from .join_probe import build_direct_table, join_probe
from .rwkv6_scan import rwkv6_scan
from .segment_reduce import segment_reduce

__all__ = ["ops", "ref", "flash_attention", "rwkv6_scan", "segment_reduce",
           "join_probe", "build_direct_table"]
