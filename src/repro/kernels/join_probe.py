"""Equi-join probe for TPU via Pallas.

The application-side join of Cobra's prefetch plans (P2: cacheByColumn +
lookup) — the TPU adaptation of a hash-table probe. Pointer-chasing hash
tables have no TPU analogue, so the build side is a direct-address table
(dense integer key space, the common case for surrogate keys): slot j holds
the row index of the build row with key j, or -1.

The TPU has no general gather from VMEM, so the kernel probes with the MXU.
The slots are laid out lane-major: key ``k`` lives in row ``k // 128``,
lane ``k % 128`` of a ``(rows, 128)`` grid, and each slot's ``row index +
1`` is split into three bytes, each exact in bf16. For every 128 probe keys
the kernel builds the one-hot ``(tile_rows, 128)`` of their grid rows and
multiplies the ``(3 * 128, tile_rows)`` byte planes by it: column ``i`` of
the product holds the 128 slots of key ``i``'s grid row. A masked sum over
the sublanes then picks lane ``k % 128``. Products of one-hot and byte
values are exact, so the result is exact up to ``2**24 - 1`` build rows.

The planes are tiled along the grid rows (second grid axis), so one tile
stays in VMEM whatever the key space; a key lives in exactly one tile, so
the tiles' contributions add up to its slot. Keys outside ``[0, M)`` match
no tile and come back -1.

Validated in interpret mode against ``ref.join_probe_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["join_probe", "build_direct_table"]

_LANES = 128
_PLANES = 3              # bytes of (row index + 1)
_TILE_ROWS = 2048        # grid rows per table tile: 1.5 MiB of bf16 planes


def build_direct_table(table_keys, key_space: int):
    """slot[j] = row index of build key j, else -1. Keys must be unique."""
    slots = jnp.full((key_space,), -1, jnp.int32)
    return slots.at[table_keys].set(jnp.arange(table_keys.shape[0],
                                               dtype=jnp.int32))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _byte_planes(table, grid_rows: int):
    """(M,) slots -> (3 * 128, grid_rows) bf16: byte p of ``slot + 1`` for
    key ``r * 128 + l`` at ``[p * 128 + l, r]``; padding slots are 0."""
    v = jnp.pad(table.astype(jnp.int32) + 1,
                (0, grid_rows * _LANES - table.shape[0]))
    v = v.reshape(grid_rows, _LANES)
    planes = jnp.stack([(v >> (8 * p)) & 0xFF for p in range(_PLANES)])
    return planes.transpose(0, 2, 1).reshape(_PLANES * _LANES, grid_rows) \
        .astype(jnp.bfloat16)


def _kernel(keys_ref, planes_ref, out_ref, *, key_rows, tile_rows):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    grid_row = ti * tile_rows + jax.lax.broadcasted_iota(
        jnp.int32, (tile_rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)

    def probe_row(j, carry):
        keys = keys_ref[pl.ds(j, 1), :]                        # (1, 128)
        onehot = (grid_row == (keys >> 7)).astype(jnp.bfloat16)
        g = jnp.dot(planes_ref[...], onehot,
                    preferred_element_type=jnp.float32)         # (384, 128)
        slot = g[:_LANES] + 256.0 * g[_LANES:2 * _LANES] \
            + 65536.0 * g[2 * _LANES:]
        hit = jnp.sum(jnp.where(lane == (keys & (_LANES - 1)), slot, 0.0),
                      axis=0, keepdims=True)
        out_ref[pl.ds(j, 1), :] += hit.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, key_rows, probe_row, 0)

    @pl.when(ti == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] -= 1                                      # 0 = empty


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def join_probe(probe_keys, table, block_n: int = 8192,
               interpret: bool = False):
    """probe_keys (N,) int32; table (M,) direct-address slots (int32).
    Returns (N,) int32 row indices into the build side, -1 when no match.
    ``block_n`` probe keys go through each grid step."""
    N = probe_keys.shape[0]
    M = table.shape[0]
    if N == 0:
        return jnp.zeros((0,), jnp.int32)
    if M == 0:
        # empty build side: every probe misses (a zero-length VMEM block
        # has no grid mapping, so short-circuit before pallas_call)
        return jnp.full((N,), -1, jnp.int32)
    rows = pl.cdiv(N, _LANES)
    key_rows = max(8, _round_up(pl.cdiv(block_n, _LANES), 8))
    if rows <= key_rows:
        key_rows = rows                     # one block spans the whole array
    rows_p = _round_up(rows, key_rows)
    keys = jnp.pad(probe_keys.astype(jnp.int32), (0, rows_p * _LANES - N),
                   constant_values=-1).reshape(rows_p, _LANES)

    grid_rows = _round_up(pl.cdiv(M, _LANES), _LANES)
    tile_rows = min(grid_rows, _TILE_ROWS)
    grid_rows = _round_up(grid_rows, tile_rows)
    planes = _byte_planes(table, grid_rows)

    out = pl.pallas_call(
        functools.partial(_kernel, key_rows=key_rows, tile_rows=tile_rows),
        grid=(rows_p // key_rows, grid_rows // tile_rows),
        in_specs=[
            pl.BlockSpec((key_rows, _LANES), lambda ni, ti: (ni, 0)),
            pl.BlockSpec((_PLANES * _LANES, tile_rows),
                         lambda ni, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((key_rows, _LANES), lambda ni, ti: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, _LANES), jnp.int32),
        interpret=interpret,
    )(keys, planes)
    return out.reshape(-1)[:N]
