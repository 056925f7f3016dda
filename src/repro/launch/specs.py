"""Input/param/cache ShapeDtypeStructs + shardings for the dry-run.

``input_specs(cfg, shape)`` produces weak-type-correct ShapeDtypeStruct
stand-ins for every model input (no device allocation), and
``plan(cfg, shape, mesh, policy)`` assembles the full lowering plan: the
step function, its argument SDS tree and the in/out shardings fitted to the
mesh (``fit_specs`` drops axes that don't divide).

Policy auto-selection: per-device bytes under plain TP =
(params + optimizer if training) / model_axis; if that exceeds the HBM
budget, parameters (and optimizer moments with them) shard additionally
over the data axis (FSDP, beyond-paper iteration recorded in §Perf).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import InputShape, ModelConfig
from repro.models.registry import WHISPER_ENC_LEN, ModelApi, get_model
from repro.sharding.policy import (
    EXPERT_TP_POLICY, FSDP_EXPERT_POLICY, FSDP_TP_POLICY, ShardingPolicy,
    TP_POLICY,
)
from repro.sharding.utils import fit_specs, tree_bytes
from repro.training.optimizer import AdamWConfig, AdamWState
from repro.training.train_loop import make_train_step

HBM_PER_CHIP = 16e9  # TPU v5e


def config_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Apply the long-context SWA override for long_500k (DESIGN §5)."""
    if shape.name == "long_500k" and cfg.long_context_window is not None:
        return dataclasses.replace(cfg, sliding_window=cfg.long_context_window)
    return cfg


def shape_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """Principled skips (recorded in DESIGN.md §5)."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, "pure full-attention arch: long_500k needs sub-quadratic attention"
    return True, ""


def param_shapes(model: ModelApi) -> Any:
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def opt_shapes(params_sds: Any) -> AdamWState:
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree.map(f32, params_sds),
        nu=jax.tree.map(f32, params_sds),
    )


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStructs for the *data* inputs of the step function."""
    b, s = shape.global_batch, shape.seq_len
    tok = lambda shp: jax.ShapeDtypeStruct(shp, jnp.int32)
    if shape.kind in ("train", "prefill"):
        if cfg.family == "encdec":
            return {
                "batch": {
                    "features": jax.ShapeDtypeStruct(
                        (b, s, cfg.enc_inputs), jnp.dtype(cfg.dtype)
                    ),
                    "tokens": tok((b, s)),
                }
            }
        return {"batch": tok((b, s))}
    # decode: ONE new token against a cache of seq_len
    model = get_model(cfg)
    return {
        "token": tok((b,)),
        "cache": model.cache_shape(b, s),
        "cache_len": jax.ShapeDtypeStruct((), jnp.int32),
    }


def select_policy(
    cfg: ModelConfig, shape: InputShape, requested: str = "auto"
) -> ShardingPolicy:
    if requested == "tp":
        return TP_POLICY
    if requested == "fsdp_tp":
        return FSDP_TP_POLICY
    if requested == "expert_tp":
        return EXPERT_TP_POLICY
    if requested == "fsdp_expert":
        return FSDP_EXPERT_POLICY
    model = get_model(cfg)
    psds = param_shapes(model)
    pbytes = tree_bytes(psds)
    model_par = 16
    per_dev = pbytes / model_par
    if shape.kind == "train":
        per_dev += 8.0 * (pbytes / jnp.dtype(cfg.param_dtype).itemsize) / model_par
    # Leave headroom for activations / caches.
    if per_dev > 0.6 * HBM_PER_CHIP:
        return FSDP_TP_POLICY
    return TP_POLICY


@dataclasses.dataclass
class LoweringPlan:
    """Everything needed to lower one (arch x shape x mesh) combination."""

    cfg: ModelConfig
    shape: InputShape
    policy: ShardingPolicy
    step_fn: Callable
    args_sds: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    kind: str


def _batch_spec(cfg: ModelConfig, shape: InputShape, policy: ShardingPolicy, mesh: Mesh):
    b = policy.physical("batch")
    if cfg.family == "encdec" and shape.kind in ("train", "prefill"):
        raw = {"features": P(b, None, None), "tokens": P(b, None)}
        sds = input_specs(cfg, shape)["batch"]
        return fit_specs(sds, raw, mesh)
    return fit_specs(
        input_specs(cfg, shape)["batch"], P(b, None), mesh
    )


def make_plan(
    cfg: ModelConfig,
    shape: InputShape,
    mesh: Mesh,
    policy_name: str = "auto",
) -> LoweringPlan:
    cfg = config_for_shape(cfg, shape)
    policy = select_policy(cfg, shape, policy_name)
    model = get_model(cfg)
    psds = param_shapes(model)
    pspec = fit_specs(psds, model.param_specs(policy), mesh)

    if shape.kind == "train":
        osds = opt_shapes(psds)
        ospec = AdamWState(step=P(), mu=pspec, nu=pspec)
        bsds = input_specs(cfg, shape)["batch"]
        bspec = _batch_spec(cfg, shape, policy, mesh)
        opt_cfg = AdamWConfig()
        step = make_train_step(model, opt_cfg, policy)
        out_shardings = (pspec, ospec, None)  # metrics replicated
        return LoweringPlan(
            cfg, shape, policy, step, (psds, osds, bsds),
            (pspec, ospec, bspec),
            out_shardings, "train",
        )

    if shape.kind == "prefill":
        bsds = input_specs(cfg, shape)["batch"]
        bspec = _batch_spec(cfg, shape, policy, mesh)
        cache_spec = fit_specs(
            model.cache_shape(shape.global_batch, shape.seq_len),
            model.cache_spec(policy), mesh,
        )

        def prefill_step(params, batch):
            return model.prefill(params, batch, policy)

        out_shardings = (None, cache_spec)
        return LoweringPlan(
            cfg, shape, policy, prefill_step, (psds, bsds),
            (pspec, bspec),
            out_shardings, "prefill",
        )

    # decode
    spec_in = input_specs(cfg, shape)
    csds = spec_in["cache"]
    cspec = fit_specs(csds, model.cache_spec(policy), mesh)
    b = policy.physical("batch")
    tok_spec = fit_specs(spec_in["token"], P(b), mesh)

    def serve_step(params, token, cache, cache_len):
        return model.decode_step(params, token, cache, cache_len, policy)

    out_shardings = (None, cspec)
    return LoweringPlan(
        cfg, shape, policy, serve_step,
        (psds, spec_in["token"], csds, spec_in["cache_len"]),
        (pspec, tok_spec, cspec, P()),
        out_shardings, "decode",
    )
