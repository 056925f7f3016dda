"""Task-graph-branched multitask models (paper §2.2 "the task graph is
retrained" + §5.3 step 3).

Binds a :class:`~repro.core.task_graph.TaskGraph` to concrete block
semantics and parameters:

* ``build_cnn_program`` — the paper-scale CNN families (benchmarks, examples,
  real-deployment reproductions);
* ``build_transformer_program`` — transformer backbones from the assigned
  architecture zoo: blocks are contiguous layer ranges, tasks are classifier
  heads on the last block's pooled hidden state (the TPU serving analogue);
* ``multitask_loss`` / joint training of all branches, which is the paper's
  "retrain the selected task graph with a multitask learning algorithm".

Both builders return a :class:`~repro.core.executor.MultitaskProgram` (for
the block-cached executor) plus a flat param pytree for training.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.executor import MultitaskProgram
from repro.core.task_graph import TaskGraph
from repro.core.types import BlockCost
from repro.models import cnn
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding.policy import ShardingPolicy, TP_POLICY

Params = Dict[str, Any]
NodeId = Tuple[int, Tuple[int, ...]]


# --------------------------------------------------------------------------
# CNN program (paper-scale)
# --------------------------------------------------------------------------

def build_cnn_program(
    key: jax.Array,
    graph: TaskGraph,
    num_classes: Sequence[int],
    input_hw: Tuple[int, int, int] = (28, 28, 1),
) -> MultitaskProgram:
    """Instantiate per-node CNN blocks + per-task heads for a task graph."""
    inits, applies, costs, feat = cnn.build_lenet5_blocks(input_hw)
    if graph.depth != len(applies):
        raise ValueError(
            f"graph depth {graph.depth} != number of CNN blocks {len(applies)}"
        )
    node_params: Dict[NodeId, Params] = {}
    for node in graph.nodes():
        d, _g = node
        key, sub = jax.random.split(key)
        node_params[node] = inits[d](sub)
    head_params = []
    for t in range(graph.num_tasks):
        key, sub = jax.random.split(key)
        head_params.append(cnn.head_init(sub, feat, num_classes[t]))
    return MultitaskProgram(
        graph=graph,
        block_fns=applies,
        node_params=node_params,
        head_fns=[cnn.head_apply] * graph.num_tasks,
        head_params=head_params,
        block_costs=costs,
    )


# --------------------------------------------------------------------------
# Transformer program (TPU-scale serving analogue)
# --------------------------------------------------------------------------

def _split_layers(num_layers: int, num_blocks: int) -> List[Tuple[int, int]]:
    """Contiguous [start, end) layer ranges, near-equal sizes."""
    base, rem = divmod(num_layers, num_blocks)
    ranges, start = [], 0
    for i in range(num_blocks):
        n = base + (1 if i < rem else 0)
        ranges.append((start, start + n))
        start += n
    return ranges


def transformer_block_costs(
    cfg: ModelConfig, ranges: Sequence[Tuple[int, int]], seq_len: int
) -> List[BlockCost]:
    """Per-block weight bytes + FLOPs for a layer-range block (per sample)."""
    bytes_per_param = jnp.dtype(cfg.param_dtype).itemsize
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    per_layer_params = (
        d * cfg.n_heads * hd          # wq
        + 2 * d * cfg.n_kv_heads * hd # wk, wv
        + cfg.n_heads * hd * d        # wo
        + (3 if cfg.activation == "swiglu" else 2) * d * f
        + 2 * d                       # norms
    )
    per_layer_flops = 2.0 * seq_len * (
        d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
        + (3 if cfg.activation == "swiglu" else 2) * d * f
    ) + 2.0 * 2.0 * seq_len * seq_len * cfg.n_heads * hd / 2.0  # causal attn
    out = []
    for (a, b) in ranges:
        n = b - a
        out.append(
            BlockCost(
                weight_bytes=float(bytes_per_param * per_layer_params * n),
                flops=float(per_layer_flops * n),
                act_bytes=float(2.0 * seq_len * d),
            )
        )
    return out


def build_transformer_program(
    key: jax.Array,
    graph: TaskGraph,
    cfg: ModelConfig,
    num_classes: Sequence[int],
    seq_len: int = 128,
    policy: ShardingPolicy = TP_POLICY,
) -> MultitaskProgram:
    """Blocks = contiguous transformer layer ranges; heads = linear probes.

    The depth-0 block also owns the token embedding table (it is always the
    root-most shared computation); no LM head is built, since no task
    decodes.  Task "heads" classify the last position's final hidden state
    — the multitask-serving analogue of the paper's per-task dense
    classifier.
    """
    from repro.models import transformer as T

    ranges = _split_layers(cfg.num_layers, graph.depth)

    def make_block_fn(depth: int):
        def apply(p: Params, x: jax.Array) -> jax.Array:
            # Built per trace, so the program can also be traced from
            # parameter shapes alone (jax.eval_shape of this function).
            q_pos = jnp.arange(seq_len, dtype=jnp.int32)
            if depth == 0:
                x = L.embed_tokens(p["embed"], x, cfg, policy)
            # Straight-line layers over separate buffers: a loop over
            # stacked weights makes XLA copy each layer's slice out of the
            # stack before its matmuls.
            for lp in p["layers"]:
                x, _, _ = T._layer_apply(lp, x, cfg, policy, q_pos)
            return x

        return apply

    def init_block(key, depth: int) -> Params:
        a, b = ranges[depth]
        n = b - a
        keys = jax.random.split(key, n)
        stacked = jax.vmap(lambda k: T._init_layer(k, cfg))(keys)
        # One parameter dict per layer, each leaf its own buffer; the draw
        # is the stacked one, so the values do not depend on the layout.
        layers = tuple(
            jax.tree.map(lambda w, i=i: w[i], stacked) for i in range(n)
        )
        p: Params = {"layers": layers}
        if depth == 0:
            p["embed"] = {"embedding": L.embed_init(
                jax.random.fold_in(key, 7), cfg.vocab_size, cfg.d_model,
                cfg.params_dtype(),
            )}
        return p

    node_params: Dict[NodeId, Params] = {}
    for node in graph.nodes():
        d, _g = node
        key, sub = jax.random.split(key)
        node_params[node] = init_block(sub, d)

    def head_fn(p: Params, x: jax.Array) -> jax.Array:
        pooled = x[:, -1].astype(jnp.float32)  # last position sees everything
        # Parameter-free standardisation: the residual stream's scale grows
        # with depth at init; without this the head starts above-chance
        # confidently wrong and training stalls.
        pooled = (pooled - pooled.mean(-1, keepdims=True)) / (
            pooled.std(-1, keepdims=True) + 1e-6
        )
        return pooled @ p["w"] + p["b"]

    head_params = []
    for t in range(graph.num_tasks):
        key, sub = jax.random.split(key)
        std = 1.0 / math.sqrt(cfg.d_model)
        head_params.append({
            "w": (std * jax.random.truncated_normal(
                sub, -2, 2, (cfg.d_model, num_classes[t])
            )).astype(jnp.float32),
            "b": jnp.zeros((num_classes[t],), jnp.float32),
        })

    costs = transformer_block_costs(cfg, ranges, seq_len)
    return MultitaskProgram(
        graph=graph,
        block_fns=[make_block_fn(d) for d in range(graph.depth)],
        node_params=node_params,
        head_fns=[head_fn] * graph.num_tasks,
        head_params=head_params,
        block_costs=costs,
    )


# --------------------------------------------------------------------------
# Joint multitask training (the paper's retraining step, [59]-style)
# --------------------------------------------------------------------------

def program_trainable_params(program: MultitaskProgram) -> Params:
    """Flat param pytree: {"nodes": {node_key: ...}, "heads": [...]}"""
    return {
        "nodes": {repr(k): v for k, v in program.node_params.items()},
        "heads": list(program.head_params),
    }


def program_with_params(program: MultitaskProgram, flat: Params) -> MultitaskProgram:
    node_params = {k: flat["nodes"][repr(k)] for k in program.node_params}
    return MultitaskProgram(
        graph=program.graph,
        block_fns=program.block_fns,
        node_params=node_params,
        head_fns=program.head_fns,
        head_params=list(flat["heads"]),
        block_costs=program.block_costs,
    )


def multitask_forward(
    program: MultitaskProgram, flat: Params, x: jax.Array
) -> List[jax.Array]:
    """Pure forward of every task (no caching — training path).

    Shared nodes appear once in ``flat`` so gradients accumulate across all
    tasks using them: that *is* branched multitask learning.
    """
    graph = program.graph
    outs = []
    # Memoise shared-prefix activations per node within this trace: the
    # compiler sees each shared block once (same effect as the runtime cache,
    # but differentiable).
    memo: Dict[str, jax.Array] = {}
    for t in range(graph.num_tasks):
        h = x
        for d, node in enumerate(graph.path(t)):
            k = repr(node)
            if k in memo:
                h = memo[k]
                continue
            h = program.block_fns[d](flat["nodes"][k], h)
            memo[k] = h
        outs.append(program.head_fns[t](flat["heads"][t], h))
    return outs


def multitask_loss(
    program: MultitaskProgram,
    flat: Params,
    x: jax.Array,
    labels: jax.Array,  # (num_tasks, B) integer labels
    task_weights: Optional[jax.Array] = None,
) -> jax.Array:
    logits = multitask_forward(program, flat, x)
    losses = []
    for t, lg in enumerate(logits):
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[t][:, None], axis=-1).mean()
        losses.append(nll)
    losses = jnp.stack(losses)
    if task_weights is not None:
        return jnp.sum(losses * task_weights) / jnp.sum(task_weights)
    return losses.mean()
