"""Jit'd public wrappers around the Pallas kernels.

Each op normalises layouts (e.g. (B,S,H,D) -> flattened (B*H,S,D) slices for
attention), handles GQA head grouping, picks block sizes, and exposes an
``interpret`` flag.  ``interpret`` defaults to ``None`` and is resolved from
the active backend (Mosaic on TPU, interpreter on CPU/GPU); an explicit bool
overrides.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.pearson_affinity import pearson_dissimilarity as _pearson
from repro.kernels.ssd_scan import ssd_scan as _ssd


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_blk", "kv_blk", "interpret")
)
def flash_attention_bhsd(
    q: jax.Array,   # (B, S, Hq, D)
    k: jax.Array,   # (B, T, Hk, D)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    q_blk: int = 128,
    kv_blk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GQA flash attention in model layout: repeats KV heads to match Q."""
    b, s, hq, d = q.shape
    hk = k.shape[2]
    rep = hq // hk
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    of = _flash(qf, kf, vf, causal=causal, window=window,
                q_blk=q_blk, kv_blk=kv_blk, interpret=interpret)
    return of.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("blk_k", "blk_f", "interpret"))
def pairwise_pearson_dissimilarity(
    feats: jax.Array,   # (K, F) raw representations of K samples
    blk_k: int = 128,
    blk_f: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Standardise rows then run the tiled ``1 - Gram`` kernel (fp32).

    ``interpret=None`` resolves from ``jax.default_backend()`` (Mosaic on
    TPU, interpreter elsewhere); an explicit bool overrides.
    """
    z = feats.astype(jnp.float32)
    z = z - jnp.mean(z, axis=-1, keepdims=True)
    z = z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-8)
    return _pearson(z, blk_k=blk_k, blk_f=blk_f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array,
    b_in: jax.Array, c_in: jax.Array,
    chunk: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    return _ssd(x, dt, a, b_in, c_in, chunk=chunk, interpret=interpret)
