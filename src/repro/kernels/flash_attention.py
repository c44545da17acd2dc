"""Blocked online-softmax attention (flash attention) for TPU via Pallas.

Targets the MXU: Q/K/V tiles live in VMEM, scores are (Bq, Bk) matmuls, and
softmax state (running max m, sum l, fp32 accumulator) is carried across
K-blocks in VMEM scratch. The grid is (B·H, Tq/Bq, Tk/Bk) — the TPU grid is
sequential in the last dimension, so the scratch carry is valid; the K/V
BlockSpec streams one (Bk, hd) tile per step (true streaming: VMEM working
set is Bq·hd + 2·Bk·hd + Bq·Bk fp32 ≈ 1–2 MB at the default 128×128 tiles,
inside the ~16 MB/core budget).

Supports causal masking, GQA (K/V index map folds the query head onto its
KV group), sliding-window (SWA) and chunked local attention (llama4-style).

Validated in interpret mode against ``ref.flash_attention_ref`` over
shape/dtype sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, chunk, block_q, block_k, n_kb, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (Bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (Bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                         # (Bq, Bk)

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if chunk is not None:
        mask &= (kpos // chunk) == (qpos // chunk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "chunk",
                                             "scale", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None, chunk: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, H, Tq, hd); k/v: (B, KV, Tk, hd). Returns (B, H, Tq, hd).

    interpret=True executes the kernel body in Python on the CPU (the
    tests); this kernel has not been compiled for the TPU."""
    B, H, Tq, hd = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    assert H % KV == 0, "GQA requires H % KV == 0"
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, "pad sequences to block multiples"
    n_kb = Tk // bk
    q_offset = Tk - Tq  # query block sits at the tail (prefill continuation)

    def kv_index(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KV + h // group, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, chunk=chunk,
        block_q=bq, block_k=bk, n_kb=n_kb, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(B * H, Tq, hd),
      k.reshape(B * KV, Tk, hd),
      v.reshape(B * KV, Tk, hd))
    return out.reshape(B, H, Tq, hd)
