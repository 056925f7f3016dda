"""The system under test, built from a configuration file and a seed.

The program is the repository's own: ``build_transformer_program`` over the
configuration's task tree, served through ``MultitaskEngine`` under the
configuration's policy.  Its weights are drawn by that same function, traced
once under ``jax.jit`` so that they are made on the device in the type they
are served in, in one call.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax

from bench.seeds import weight_key
from repro.core.task_graph import TaskGraph
from repro.models.config import ModelConfig, make_config
from repro.models.multitask import (
    build_transformer_program, program_trainable_params, program_with_params,
)
from repro.serving import (
    AffinityPolicy, EnginePolicy, MultitaskEngine, RequestGroupScheduler,
)

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


def build_program(cfg: Dict, seed: int):
    """The task tree's program with its weights made on the device."""
    graph = TaskGraph.from_groups(cfg["tree"])
    mcfg = model_config(cfg)
    built = {}

    def init(key):
        prog = build_transformer_program(
            key, graph, mcfg, cfg["num_classes"], seq_len=cfg["seq_len"]
        )
        built["program"] = prog
        return program_trainable_params(prog)

    params = jax.jit(init)(weight_key(seed))
    # The traced program lends its block and head functions; the weights
    # are the arrays the jitted call returned.
    return program_with_params(built["program"], params)


def build_engine(program, cfg: Dict) -> MultitaskEngine:
    policy = cfg["policy"]
    if policy.get("adaptive") is not None:
        raise ValueError("adaptive gating is not part of these deployments")
    return MultitaskEngine(program, policy=EnginePolicy(
        warm_start=bool(policy["warm_start"]),
        group_ordering=bool(policy["group_ordering"]),
        resolve_order_per_plan=bool(policy["resolve_order_per_plan"]),
        scheduling=AffinityPolicy(
            max_group_size=int(policy["max_group_size"]),
            max_wait=float(policy["max_wait_s"]),
        ),
        scheduler=RequestGroupScheduler(
            batch_shapes=tuple(int(s) for s in policy["batch_shapes"])
        ),
    ))


def batch_shapes(cfg: Dict) -> Tuple[int, ...]:
    return tuple(sorted(int(s) for s in cfg["policy"]["batch_shapes"]))


def padded_shape(cfg: Dict, valid: int) -> int:
    """The batch shape a group of ``valid`` requests runs at."""
    return next(s for s in batch_shapes(cfg) if s >= valid)
