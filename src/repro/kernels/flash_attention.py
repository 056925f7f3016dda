"""Pallas TPU flash attention (online-softmax, causal/sliding-window).

Target: TPU v5e.  Grid ``(batch*kv_heads*q_groups, S/q_blk, T/kv_blk)`` with
the KV axis innermost — TPU grids execute sequentially, so the running
softmax statistics live in VMEM scratch across KV steps and the output tile
is finalised on the last KV step.  Block shapes keep the working set in
VMEM: ``q_blk x d`` + ``kv_blk x d`` tiles plus an ``q_blk x kv_blk`` score
tile, all multiples of 128 on the matmul dims for MXU alignment.

The kernel is validated in interpret mode against
:func:`repro.kernels.ref.flash_attention_ref` (and the model-side oracle
``repro.models.layers.attention_chunked``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pearson_affinity import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,          # (1, q_blk, d), (1, kv_blk, d) VMEM tiles
    o_ref,                        # (1, q_blk, d)
    m_scr, l_scr, acc_scr,        # VMEM scratch: (q_blk,), (q_blk,), (q_blk, d)
    *,
    sm_scale: float,
    q_blk: int,
    kv_blk: int,
    kv_len: int,
    causal: bool,
    window: Optional[int],
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * sm_scale          # (q_blk, d)
    k = k_ref[0].astype(jnp.float32)                     # (kv_blk, d)
    s = q @ k.T                                          # (q_blk, kv_blk)

    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
    k_pos = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + p.sum(axis=1)
    acc = acc_scr[...] * alpha[:, None] + p @ v_ref[0].astype(jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


def flash_attention(
    q: jax.Array,                 # (BH, S, d) queries, flattened batch*heads
    k: jax.Array,                 # (BH, T, d)
    v: jax.Array,                 # (BH, T, d)
    causal: bool = True,
    window: Optional[int] = None,
    q_blk: int = 128,
    kv_blk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas flash attention over flattened (batch*heads) slices.

    Sequence lengths are padded to the block sizes; padding keys are masked
    by the in-kernel ``k_pos < kv_len`` guard and padded queries sliced off.
    ``interpret=None`` resolves from the backend (Mosaic on TPU, interpreter
    elsewhere); pass an explicit bool to override.
    """
    interpret = resolve_interpret(interpret)
    bh, s, d = q.shape
    t = k.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    s_pad = (s + q_blk - 1) // q_blk * q_blk
    t_pad = (t + kv_blk - 1) // kv_blk * kv_blk
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0)))

    grid = (bh, s_pad // q_blk, t_pad // kv_blk)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale, q_blk=q_blk, kv_blk=kv_blk,
        kv_len=t, causal=causal, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_blk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_blk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s]
