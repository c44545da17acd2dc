"""Segment reduction (relational γ group-by aggregation) for TPU via Pallas.

Cobra's hottest relational operator after the join. The TPU adaptation of
hash-based grouping (which needs pointer chasing — no TPU analogue): rows
arrive lane-dense as ``(rows, 128)`` blocks, and for each row of 128 values
the kernel compares their segment ids against a ``(block_g, 128)`` iota of
group ids and folds the masked values into a ``(block_g, 128)`` accumulator
on the VPU — groups on sublanes, row positions on lanes. The accumulator is
the kernel's output; one reduction over the lanes outside the kernel gives
each group's total. Larger G is tiled on the first grid axis.

The fold stays on the VPU in float32: integer-valued sums are exact while
every partial sum stays below ``2**24``, whatever the order, which is the
exactness the compiled tier's fold gate relies on (an MXU matmul at default
precision would round the values to bf16 first).

VMEM per step: two ``(block_n / 128, 128)`` input blocks and the
``(block_g, 128)`` float32 accumulator — 512 KiB at ``block_g = 1024``.

Validated in interpret mode against ``ref.segment_reduce_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["segment_reduce"]

_LANES = 128
_IDENTITY = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(v_ref, s_ref, o_ref, *, op, rows, block_g):
    gi = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _IDENTITY[op])

    gids = gi * block_g + jax.lax.broadcasted_iota(
        jnp.int32, (block_g, _LANES), 0)

    def fold_row(j, carry):
        vals = v_ref[pl.ds(j, 1), :]                           # (1, 128)
        hit = gids == s_ref[pl.ds(j, 1), :]                    # (Bg, 128)
        if op == "sum":
            o_ref[...] += jnp.where(hit, vals, 0.0)
        elif op == "min":
            o_ref[...] = jnp.minimum(o_ref[...], jnp.where(hit, vals, jnp.inf))
        else:
            o_ref[...] = jnp.maximum(o_ref[...],
                                     jnp.where(hit, vals, -jnp.inf))
        return carry

    jax.lax.fori_loop(0, rows, fold_row, 0)


@functools.partial(jax.jit, static_argnames=("num_segments", "op", "block_n",
                                             "block_g", "interpret"))
def segment_reduce(values, segment_ids, num_segments: int, op: str = "sum",
                   block_n: int = 65536, block_g: int = 1024,
                   interpret: bool = False):
    """values (N,) float; segment_ids (N,) int32 in [0, num_segments).
    Returns (num_segments,) float32 aggregation. ``block_n`` rows and
    ``block_g`` groups go through each grid step."""
    if op not in ("sum", "count", "min", "max"):
        raise ValueError(op)
    N = values.shape[0]
    if num_segments == 0:
        return jnp.zeros((0,), jnp.float32)
    if N == 0:
        # every group is empty: sum/count identity is 0, and the min/max
        # convention below maps empty groups to 0 as well
        return jnp.zeros((num_segments,), jnp.float32)
    if op == "count":
        values, op = jnp.ones((N,), jnp.float32), "sum"
    rows = pl.cdiv(N, _LANES)
    block_rows = max(8, _round_up(pl.cdiv(block_n, _LANES), 8))
    if rows <= block_rows:
        block_rows = rows                   # one block spans the whole array
    rows_p = _round_up(rows, block_rows)
    pad = rows_p * _LANES - N
    values = jnp.pad(values.astype(jnp.float32), (0, pad)) \
        .reshape(rows_p, _LANES)
    # padded rows carry segment -1, which no group id matches
    segment_ids = jnp.pad(segment_ids.astype(jnp.int32), (0, pad),
                          constant_values=-1).reshape(rows_p, _LANES)
    bg = _round_up(min(block_g, num_segments), 8)
    G = _round_up(num_segments, bg)

    acc = pl.pallas_call(
        functools.partial(_kernel, op=op, rows=block_rows, block_g=bg),
        grid=(G // bg, rows_p // block_rows),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda gi, ni: (ni, 0)),
            pl.BlockSpec((block_rows, _LANES), lambda gi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((bg, _LANES), lambda gi, ni: (gi, 0)),
        out_shape=jax.ShapeDtypeStruct((G, _LANES), jnp.float32),
        interpret=interpret,
    )(values, segment_ids)
    reduce = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    out = reduce(acc, axis=1)[:num_segments]
    if op in ("min", "max"):
        out = jnp.where(jnp.isfinite(out), out, 0.0)  # empty groups → 0
    return out
