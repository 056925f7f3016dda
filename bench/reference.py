"""The plain reference of a task-tree configuration, kept with the benchmark.

It imports nothing of the program and takes nothing the program made.  It
draws its own weights from the seed, by the recipe the configuration states
(the draws of ``build_transformer_program`` at the time the benchmark was
defined: per tree node in canonical order, truncated-normal fan-in
projections and a normal(0.02) embedding, cast to the parameter type), and
computes each requested task on its own: the embedding, every decoder layer
on the task's root-to-leaf path, then the task's head.  No activation cache,
no fused suffix, no batching: one prompt at a time, one tree node at a time.

A decoder layer is Mistral's (RMSNorm, rotary attention with grouped KV
heads, causal softmax, SwiGLU feed-forward), as in the published
``config.json``.  A task head classifies the last position's hidden state,
standardised over the hidden axis, with a float32 linear probe.

``mode="f32"`` is the reference: every operation in float32 and every
matrix product at ``Precision.HIGHEST``.  ``mode="fp8"`` is the control:
the same, with both inputs of every matrix product rounded to fp8 (e4m3,
one scale per tensor, as fp8 serving does), the step below the bfloat16
that the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import canonical_tree, path
from bench.seeds import weight_key

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


# ----------------------------------------------------------------- weights

def _dense(key, in_dim: int, out_shape: Tuple[int, ...], dtype) -> jax.Array:
    std = 1.0 / math.sqrt(in_dim)
    return (std * jax.random.truncated_normal(
        key, -2.0, 2.0, (in_dim,) + tuple(out_shape))).astype(dtype)


def _layer_weights(key, cfg: Dict) -> Dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    dt = jnp.dtype(cfg["torch_dtype"])
    ka, km = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    k1, k2, k3 = jax.random.split(km, 3)
    return {
        "attn_norm": jnp.ones((d,), dt),
        "mlp_norm": jnp.ones((d,), dt),
        "wq": _dense(kq, d, (h, hd), dt),
        "wk": _dense(kk, d, (hk, hd), dt),
        "wv": _dense(kv, d, (hk, hd), dt),
        "wo": _dense(ko, h * hd, (d,), dt),
        "w_gate": _dense(k1, d, (f,), dt),
        "w_up": _dense(k2, d, (f,), dt),
        "w_down": _dense(k3, f, (d,), dt),
    }


def init_weights(cfg: Dict, key: jax.Array) -> Dict:
    """Every node's stacked layers (and the embedding, at depth 0) and every
    task head, drawn in the configuration's recipe."""
    dt = jnp.dtype(cfg["torch_dtype"])
    nodes = {}
    for d, groups in enumerate(canonical_tree(cfg["tree"])):
        for g in groups:
            key, sub = jax.random.split(key)
            n = cfg["layers_per_depth"][d]
            p = {"layers": jax.vmap(lambda k: _layer_weights(k, cfg))(
                jax.random.split(sub, n))}
            if d == 0:
                p["embed"] = (jax.random.normal(
                    jax.random.fold_in(sub, 7),
                    (cfg["vocab_size"], cfg["hidden_size"])) * 0.02).astype(dt)
            nodes[repr((d, g))] = p
    heads = []
    for c in cfg["num_classes"]:
        key, sub = jax.random.split(key)
        heads.append({"w": _dense(sub, cfg["hidden_size"], (c,), jnp.float32),
                      "b": jnp.zeros((c,), jnp.float32)})
    return {"nodes": nodes, "heads": heads}


# ------------------------------------------------------------ arithmetic

def fp8_e4m3(y: jax.Array) -> jax.Array:
    """Round float32 values within +-448 to the nearest float8_e4m3fn value
    (4 significant bits, smallest normal 2**-6, subnormal step 2**-9)."""
    _m, e = jnp.frexp(y)
    e = jnp.maximum(e, -5)
    q = jnp.ldexp(jnp.round(jnp.ldexp(y, 4 - e)), e - 4)
    return jnp.clip(q, -FP8_MAX, FP8_MAX)


def _scaled_fp8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return fp8_e4m3(x / scale), scale


def product(spec: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HI)
    if mode == "fp8":
        (qa, sa), (qb, sb) = _scaled_fp8(a), _scaled_fp8(b)
        return jnp.einsum(spec, qa, qb, precision=HI) * (sa * sb)
    raise ValueError(f"unknown mode {mode!r}")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding, rotating the two halves of each head (Mistral)."""
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, w, cfg: Dict, mode: str):
    """One Mistral decoder layer on one prompt ``x`` of shape (S, hidden)."""
    f32 = lambda a: a.astype(jnp.float32)
    h_, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    h = _rmsnorm(x, f32(w["attn_norm"]), eps)
    q = _rope(product("sd,dhk->shk", h, w["wq"], mode), theta)
    k = _rope(product("sd,dhk->shk", h, w["wk"], mode), theta)
    v = product("sd,dhk->shk", h, w["wv"], mode)
    k = jnp.repeat(k, h_ // hk, axis=1)  # query head i reads KV head i // (H/Hk)
    v = jnp.repeat(v, h_ // hk, axis=1)
    scores = product("shk,thk->hst", q, k, mode) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = product("hst,thk->shk", att, v, mode)
    x = x + product("shk,hkd->sd", o, w["wo"].reshape(h_, hd, -1), mode)
    h = _rmsnorm(x, f32(w["mlp_norm"]), eps)
    a = jax.nn.silu(product("sd,df->sf", h, w["w_gate"], mode)) * product(
        "sd,df->sf", h, w["w_up"], mode)
    return x + product("sf,fd->sd", a, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _node(layers, x, cfg_key, mode):
    cfg = dict(cfg_key)

    def body(h, w):
        return _layer(h, w, cfg, mode), None

    return jax.lax.scan(body, x, layers)[0]


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mode",))
def _head(head, x, mode):
    pooled = x[-1]
    pooled = (pooled - pooled.mean()) / (pooled.std() + 1e-6)
    return product("d,dc->c", pooled, head["w"], mode) + head["b"]


def _cfg_key(cfg: Dict) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


class Reference:
    """The configuration's weights for one seed, and its forward pass."""

    def __init__(self, cfg: Dict, seed: int):
        self.cfg = cfg
        self.weights = jax.jit(functools.partial(init_weights, cfg))(weight_key(seed))

    def logits(self, prompt: np.ndarray, tasks: Sequence[int],
               mode: str = "f32") -> Dict[int, np.ndarray]:
        """Each requested task's logits for one prompt of token ids."""
        cfg, w = self.cfg, self.weights
        out = {}
        with jax.default_matmul_precision("highest"):
            for t in tasks:
                nodes = path(cfg["tree"], t)
                x = _embed(w["nodes"][repr(nodes[0])]["embed"], jnp.asarray(prompt))
                for node in nodes:
                    x = _node(w["nodes"][repr(node)]["layers"], x, _cfg_key(cfg), mode)
                out[t] = np.asarray(_head(w["heads"][t], x, mode), np.float32)
        return out

    def close(self) -> None:
        """Drop the weights, so the device memory is free for what follows."""
        self.weights = None
