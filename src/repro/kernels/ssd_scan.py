"""Pallas TPU kernel for the Mamba2 chunked SSD scan (arXiv:2405.21060).

One grid step processes one (batch, head, chunk) tile: the intra-chunk
quadratic term (masked-decay attention over the chunk) and the inter-chunk
state recurrence, with the head's running state carried in VMEM scratch
across the sequential chunk axis.  Grid ``(B, H, S/Q)`` with the chunk axis
innermost; the state scratch is re-zeroed at chunk 0 of every (batch, head).

Every in-kernel operation is a 2-D elementwise op, a row broadcast or a 2-D
matmul, which is what Mosaic lowers.  The wrapper does the layout work in
XLA: it forms ``dt * x`` and ``dt * a`` and hands the kernel head-major
tiles.  Prefix sums over the chunk are triangular-ones matmuls, since Mosaic
has no cumsum.  On TPU the chunk must be a multiple of 128: it is the lane
dimension of the ``dt * a`` and ``B^T`` tiles.

Per-tile working set (fp32): Q*P (dt*x) + 2*Q*N (B, C) + N*P (state) + a few
Q*Q decay tiles — well inside VMEM for (Q=128, P=64, N=128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pearson_affinity import resolve_interpret

_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(
    dx_ref,     # (Q, P)  dt * x of this head
    da_ref,     # (1, Q)  dt * a of this head
    bt_ref,     # (N, Q)  B transposed
    c_ref,      # (Q, N)
    y_ref,      # (Q, P)
    fin_ref,    # (N, P)  final state (transposed), written on the last chunk
    state_scr,  # VMEM (N, P) running inter-chunk state (transposed)
    *,
    q: int,
    nc: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _reset():
        state_scr[...] = jnp.zeros_like(state_scr)

    dx = dx_ref[...].astype(jnp.float32)
    da = da_ref[...].astype(jnp.float32)
    bt = bt_ref[...].astype(jnp.float32)
    cc = c_ref[...].astype(jnp.float32)
    p = dx.shape[1]

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = row >= col
    # Inclusive prefix sums cum_i = sum_{k<=i} da_k, as matmuls:
    #   (causal * da) @ ones  -> cum_i along row i (any width);
    #   broadcast(da) @ causal^T -> cum_j along column j.
    tri_da = jnp.where(causal, da, 0.0)
    cum_i = jnp.dot(tri_da, jnp.ones((q, q), jnp.float32), precision=_HI)
    cum_ip = jnp.dot(tri_da, jnp.ones((q, p), jnp.float32), precision=_HI)
    cum_j = jnp.dot(
        jnp.broadcast_to(da, (q, q)), (row <= col).astype(jnp.float32),
        precision=_HI,
    )
    cum_last = cum_ip[q - 1:q, :]                                  # (1, P)

    # Intra-chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    cb = jnp.dot(cc, bt)                                           # (Q, Q)
    lmat = jnp.where(causal, jnp.exp(cum_i - cum_j), 0.0) * cb
    y = jnp.dot(lmat, dx)                                          # (Q, P)

    # Inter-chunk: y_i += exp(cum_i) * C_i . state_prev
    state = state_scr[...]                                         # (N, P)
    y += jnp.exp(cum_ip) * jnp.dot(cc, state)

    # State update: state = state * exp(cum_Q) + sum_j B_j dt_j x_j exp(cum_Q - cum_j)
    state_scr[...] = state * jnp.exp(cum_last) + jnp.dot(
        bt, dx * jnp.exp(cum_last - cum_ip)
    )

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        fin_ref[...] = state_scr[...].astype(fin_ref.dtype)


def ssd_scan(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    a: jax.Array,      # (H,)
    b_in: jax.Array,   # (B, S, N)
    c_in: jax.Array,   # (B, S, N)
    chunk: int = 128,
    interpret: Optional[bool] = None,
):
    """Chunked SSD.  Returns (y (B,S,H,P), final_state (B,H,P,N) fp32).

    ``interpret=None`` resolves from the backend (Mosaic on TPU, interpreter
    elsewhere); pass an explicit bool to override.
    """
    interpret = resolve_interpret(interpret)
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    if s % chunk != 0:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
    s_pad = x.shape[1]
    nc = s_pad // chunk
    dt32 = dt.astype(jnp.float32)
    dx = (x.astype(jnp.float32) * dt32[..., None]).transpose(0, 2, 1, 3)
    da = (dt32 * a.astype(jnp.float32)).transpose(0, 2, 1)[:, :, None, :]
    bt = b_in.transpose(0, 2, 1)
    y, fin = pl.pallas_call(
        functools.partial(_ssd_kernel, q=chunk, nc=nc),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, hh, c: (b, hh, 0, c)),
            pl.BlockSpec((None, n, chunk), lambda b, hh, c: (b, 0, c)),
            pl.BlockSpec((None, chunk, n), lambda b, hh, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, n, p), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s_pad, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(dx, da, bt, c_in)
    return y.transpose(0, 2, 1, 3)[:, :s], fin.transpose(0, 1, 3, 2)
