"""Mistral (``model_type`` "mistral"): the program's configuration object, the
plain reference of one tree node, and its required FLOPs.

A decoder layer is Mistral's, as in the published ``config.json``: RMSNorm,
rotary attention with grouped KV heads, causal softmax, SwiGLU feed-forward.
A node is ``layers_per_depth[depth]`` such layers.  Only ``model_config``
touches the program (its configuration class); the reference's weights and
forward pass use nothing of it.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference import dense, product, rmsnorm, rope
from repro.models.config import ModelConfig, make_config

ACTIVATIONS = {"silu": "swiglu"}


def model_config(cfg: Dict) -> ModelConfig:
    """The program's configuration object, from the published key names."""
    if cfg["hidden_act"] not in ACTIVATIONS:
        raise ValueError(f"unsupported hidden_act {cfg['hidden_act']!r}")
    if cfg.get("sliding_window") is not None:
        raise ValueError("sliding-window attention is not served by this path")
    if sum(cfg["layers_per_depth"]) != cfg["num_hidden_layers"] or len(
            cfg["layers_per_depth"]) != len(cfg["tree"]):
        raise ValueError("layers_per_depth must split num_hidden_layers over the tree")
    return make_config(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], activation=ACTIVATIONS[cfg["hidden_act"]],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
    )


# ----------------------------------------------------------------- weights

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
        "wq": dense(kq, d, (h, hd), dt),
        "wk": dense(kk, d, (hk, hd), dt),
        "wv": dense(kv, d, (hk, hd), dt),
        "wo": dense(ko, h * hd, (d,), dt),
        "w_gate": dense(k1, d, (f,), dt),
        "w_up": dense(k2, d, (f,), dt),
        "w_down": dense(k3, f, (d,), dt),
    }


def init_node(key, cfg: Dict, depth: int) -> Dict:
    """The node's layers, stacked on a leading axis."""
    n = cfg["layers_per_depth"][depth]
    return jax.vmap(lambda k: _layer_weights(k, cfg))(jax.random.split(key, n))


# ------------------------------------------------------------ arithmetic

def _layer(x, w, cfg: Dict, mode: str):
    """One Mistral decoder layer on one prompt ``x`` of shape (S, hidden)."""
    f32 = lambda a: a.astype(jnp.float32)
    h_, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    h = rmsnorm(x, f32(w["attn_norm"]), eps)
    q = rope(product("sd,dhk->shk", h, w["wq"], mode), theta)
    k = rope(product("sd,dhk->shk", h, w["wk"], mode), theta)
    v = product("sd,dhk->shk", h, w["wv"], mode)
    k = jnp.repeat(k, h_ // hk, axis=1)  # query head i reads KV head i // (H/Hk)
    v = jnp.repeat(v, h_ // hk, axis=1)
    scores = product("shk,thk->hst", q, k, mode) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = product("hst,thk->shk", att, v, mode)
    x = x + product("shk,hkd->sd", o, w["wo"].reshape(h_, hd, -1), mode)
    h = rmsnorm(x, f32(w["mlp_norm"]), eps)
    a = jax.nn.silu(product("sd,df->sf", h, w["w_gate"], mode)) * product(
        "sd,df->sf", h, w["w_up"], mode)
    return x + product("sf,fd->sd", a, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _node(layers, x, cfg_key, mode):
    cfg = dict(cfg_key)

    def body(h, w):
        return _layer(h, w, cfg, mode), None

    return jax.lax.scan(body, x, layers)[0]


def _cfg_key(cfg: Dict) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def apply_node(params, x, cfg: Dict, depth: int, mode: str):
    """The node's layers, in order, on one prompt's float32 hidden states."""
    return _node(params, x, _cfg_key(cfg), mode)


# ----------------------------------------------------------------- FLOPs

def layer_flops(cfg: Dict) -> float:
    """One decoder layer over one prompt of ``seq_len`` tokens: its
    projection and feed-forward matmuls for every token and causal attention
    (half of the score matrix and half of its product with V)."""
    s, d, f = cfg["seq_len"], cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    matmul_params = d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * f
    causal_attention = 2.0 * s * s * h * hd  # QK^T and PV, half the scores each
    return 2.0 * s * matmul_params + causal_attention


def node_flops(cfg: Dict, depth: int) -> float:
    return cfg["layers_per_depth"][depth] * layer_flops(cfg)
