"""Logical-axis sharding policy.

Model code annotates tensors with *logical* axes (``batch``, ``model``,
``fsdp``, ``expert``, ``seq``); the policy maps those to physical mesh axes.
This keeps every model family mesh-agnostic: the same code lowers on the
single-pod ``(data, model)`` mesh, the multi-pod ``(pod, data, model)`` mesh,
and on a single CPU device (where constraints are no-ops).

Two built-in policies:

* ``TP_POLICY`` — the paper-faithful-era baseline: tensor parallelism over
  ``model``, batch over ``data`` (+ ``pod``), parameters replicated across
  data.  Sufficient for every arch except 340B-scale training.
* ``FSDP_TP_POLICY`` — beyond-paper: parameters additionally sharded over
  the data axis (ZeRO-3 style); the per-layer all-gather is amortised by
  the layer scan.  Required for nemotron-4-340b training to fit HBM
  (recorded as a §Perf iteration).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Logical = Union[None, str, Tuple[str, ...]]


def _ambient_mesh():
    """The mesh installed by ``jax.set_mesh``, or None outside one.

    A failing accessor raises: swallowing it would silently degrade every
    spec to replicated.
    """
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Maps logical tensor axes to physical mesh axes.

    Attributes:
      batch: mesh axes carrying the batch (``("data",)`` or
        ``("pod", "data")``).
      model: mesh axis for tensor parallelism (heads / d_ff / vocab).
      fsdp: mesh axis over which parameters are additionally sharded
        (None = replicated across data — the baseline).
      expert: mesh axis for expert parallelism of MoE stacks (None = experts
        co-located, TP inside each expert — the baseline).
    """

    name: str
    batch: Tuple[str, ...] = ("data",)
    model: Optional[str] = "model"
    fsdp: Optional[str] = None
    expert: Optional[str] = None

    def physical(self, logical: Logical):
        """Resolve one logical axis to mesh axes (or None)."""
        if logical is None:
            return None
        if logical == "batch":
            return self.batch if len(self.batch) > 1 else self.batch[0]
        if logical == "model":
            return self.model
        if logical == "fsdp":
            return self.fsdp
        if logical == "expert":
            return self.expert
        if logical == "seq":
            return None  # sequence never sharded in this framework
        raise ValueError(f"unknown logical axis {logical!r}")

    def spec(self, *logical_axes: Logical) -> P:
        """PartitionSpec from logical axes, dropping axes absent from the
        ambient mesh (lets the same model run on 1-device CPU)."""
        mesh = _ambient_mesh()
        names = set(mesh.axis_names) if mesh is not None else set()

        def keep(ax):
            if ax is None:
                return None
            if isinstance(ax, tuple):
                kept = tuple(a for a in ax if a in names)
                return kept if kept else None
            return ax if ax in names else None

        return P(*[keep(self.physical(a)) for a in logical_axes])

    def param_spec(self, shape: Sequence[int]) -> P:
        """Ideal weight layout for one parameter leaf of ``shape``.

        Convention for the task-graph serving path: matrices (and higher)
        shard their first axis over ``fsdp`` (ZeRO-style, None under TP) and
        their last axis over ``model`` (tensor parallelism); vectors and
        scalars replicate.  Callers pass the result through
        ``repro.sharding.utils.fit_spec`` so axes absent from the concrete
        mesh — or not dividing the dimension — degrade to replication.
        """
        nd = len(shape)
        if nd < 2:
            return P(*([None] * nd))
        return P(self.fsdp, *([None] * (nd - 2)), self.model)

    def data_shards(self, mesh) -> int:
        """How many ways the batch dimension splits on ``mesh`` (the
        per-shard multiple the request-group scheduler must pad to)."""
        if mesh is None:
            return 1
        names = set(mesh.axis_names)
        n = 1
        for a in self.batch:
            if a in names:
                n *= int(mesh.shape[a])
        return n

    def weight_shards(self, mesh) -> int:
        """How many ways parameters split on ``mesh`` (the divisor on the
        cost model's weight-load term: each chip streams only its slice)."""
        if mesh is None:
            return 1
        names = set(mesh.axis_names)
        n = 1
        for a in sorted({a for a in (self.model, self.fsdp) if a is not None}):
            if a in names:
                n *= int(mesh.shape[a])
        return n


TP_POLICY = ShardingPolicy(name="tp", batch=("pod", "data"))
FSDP_TP_POLICY = ShardingPolicy(
    name="fsdp_tp", batch=("pod", "data"), fsdp="data"
)
EXPERT_TP_POLICY = ShardingPolicy(
    name="expert_tp", batch=("pod", "data"), expert="model"
)
FSDP_EXPERT_POLICY = ShardingPolicy(
    name="fsdp_expert", batch=("pod", "data"), fsdp="data", expert="model"
)

POLICIES = {
    p.name: p
    for p in (TP_POLICY, FSDP_TP_POLICY, EXPERT_TP_POLICY, FSDP_EXPERT_POLICY)
}


def shard_act(x: jax.Array, policy: ShardingPolicy, *logical_axes: Logical):
    """``with_sharding_constraint`` on activations; no-op without a mesh."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = policy.spec(*logical_axes)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)
