"""Block-cached task-graph executor (paper §2.3).

The runtime mirrors the paper's MCU design, one level up the memory
hierarchy:

* a *static buffer* holds exactly one common-architecture's worth of blocks
  (one resident block per depth).  Before executing task ``t``, each block on
  ``t``'s path is loaded into its depth slot **unless it is already
  resident** — the "skip loading blocks already in main memory" rule;
* one *activation buffer per depth* caches the output of the most recently
  executed block at that depth, so a task sharing a prefix with the
  previously-run task resumes from the deepest shared block — the "reuse
  intermediate results" rule;
* tasks with conditional prerequisites may be *skipped at runtime* based on
  a gate over previously produced results (paper §4.3's conditional
  constraints), which skips their entire non-shared suffix.

The executor is generic over block semantics: it takes callables, so the
same engine drives the CNN-scale paper benchmarks and the transformer-scale
serving path.

Dispatch strategy: by default each contiguous non-shared suffix
(resume-depth -> head) is compiled into a **single fused program** keyed by
``(task, resume_depth, batched, input shape)`` — one dispatch per task
instead of one per block.  When the suffix's blocks are homogeneous (same
apply function, same parameter shapes, shape-preserving) the fused program
stacks the suffix's parameters and drives them with ``lax.scan``; otherwise
the suffix is unrolled inside one jitted program.  ``fused=False`` keeps the
original per-block dispatch path as the reference implementation; both paths
produce identical counters and (allclose-)identical outputs, which the tests
assert.  The compile cache is bounded by the same fixed-shape discipline the
request-group scheduler enforces: tasks x (depth+1) resume points x the
scheduler's padded batch shapes.

Warm starts: :meth:`TaskGraphExecutor.residency_state` exposes the per-depth
resident blocks so callers (the serving engine, the cost model) can account
cross-group weight-load reuse; :meth:`clear_activations` is the warm-start
entry point — it invalidates input-dependent activation caches while keeping
the input-independent weight residency, so a new request group resumes with
the previous group's blocks still "in memory".

``ExecutionStats`` counters must match ``GraphCostModel.predicted_stats``
exactly (including warm starts via its ``resume`` argument); property tests
assert this for random graphs, orders, and multi-group plans.

Request *groups* execute through :meth:`TaskGraphExecutor.run_batch`: the
same residency/prefix-reuse logic, but every block is vmapped over a stacked
batch of requests so one weight load (and one fused dispatch) serves the
whole group.  The batched counters match
``GraphCostModel.predicted_stats(order, batch_size=B)``.
"""
from __future__ import annotations

import dataclasses
from typing import (
    Any, Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.constraints import Constraints
from repro.core.spans import span
from repro.core.task_graph import TaskGraph
from repro.core.types import (
    BlockCost, ExecutionStats, NodeId, TaskGateRecord,
)
from repro.sharding.policy import ShardingPolicy, TP_POLICY
from repro.sharding.utils import fit_spec

# What residency_state returns and what GraphCostModel.predicted_stats
# accepts as ``resume`` (the concrete tuple form of types.Residency).
ResidencyState = Tuple[Optional[NodeId], ...]

# block_fns[d](params, x) -> y  for depth-d blocks of the common architecture
BlockFn = Callable[[Any, jnp.ndarray], jnp.ndarray]
# head_fn(params, y) -> task output
HeadFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


@dataclasses.dataclass
class MultitaskProgram:
    """A task graph bound to parameters and block semantics.

    Attributes:
      graph: the task graph.
      block_fns: per-depth apply function of the common architecture.
      node_params: parameters for every ``(depth, group)`` block node.
      head_fns / head_params: per-task classifier heads (the per-task leaf
        the paper attaches after the last shared block).
      block_costs: per-depth cost entries used for stats accounting.
    """

    graph: TaskGraph
    block_fns: Sequence[BlockFn]
    node_params: Dict[NodeId, Any]
    head_fns: Sequence[HeadFn]
    head_params: Sequence[Any]
    block_costs: Sequence[BlockCost]

    def __post_init__(self) -> None:
        for node in self.graph.nodes():
            if node not in self.node_params:
                raise ValueError(f"missing params for task-graph node {node}")


def _leaf_specs(params: Any) -> Tuple:
    """(treedef, leaf shapes/dtypes) fingerprint for stackability checks."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return treedef, tuple((jnp.shape(l), jnp.result_type(l)) for l in leaves)


def _named(fn: Callable, name: str, batched: bool,
           shape: Tuple[int, ...]) -> Callable:
    """``fn`` renamed ``<name>_b<rows>`` (``<name>`` unbatched): ``jax.jit``
    names its XLA module ``jit_<name>...``, so the device trace says which
    depths and batch shape ran.  The task is left out on purpose: tasks at
    one resume depth and batch shape run the same HLO, and the module name
    is part of the persistent compilation cache's key, so naming the task
    would compile and store that program once per task."""
    fn.__name__ = fn.__qualname__ = (
        f"{name}_b{shape[0]}" if batched else name)
    return fn


def _gate_bcast(fire: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Reshape a per-row ``(B,)`` fire mask to broadcast against ``y``
    (``(B, ...)``) inside ``jnp.where``; scalar masks broadcast as-is."""
    if jnp.ndim(fire) == 0:
        return fire
    return fire.reshape(fire.shape + (1,) * (jnp.ndim(y) - jnp.ndim(fire)))


@dataclasses.dataclass
class ActivationCheckpoint:
    """A mid-suffix activation snapshot at a block-depth boundary.

    ``value`` is the cached activation of ``node`` (the block at ``depth``
    on the interrupted task's path) and ``act_shape`` the input-shape guard
    it was produced under (``TaskGraphExecutor._act_shape``).  Restoring it
    (:meth:`TaskGraphExecutor.restore_activation`) makes the next matching
    task resume from ``depth + 1`` instead of 0 — the paper's "an inference
    interrupted at block k must not restart from block 0" property.
    """

    depth: int
    node: NodeId
    value: Any
    act_shape: Optional[Tuple[int, ...]] = None


class WeightStreamer:
    """Double-buffered asynchronous host->device weight stager.

    One staging slot per executor: :meth:`stage` issues non-blocking
    ``jax.device_put`` copies for the *next* plan's non-resident block
    params (JAX dispatch is asynchronous, so the transfers overlap with
    whatever fused suffix is still executing on the device), replacing any
    previous batch — stage(k+1) while executing k is the double buffer.

    Commit-on-use: a staged copy only becomes the executor's parameter for
    its node when the executor actually loads that node
    (:meth:`commit`, called from the load branch of ``_run_task_impl``).
    Until then nothing observable changes, so cancellation —
    :meth:`cancel` on a fresh stage, :meth:`invalidate` from
    ``TaskGraphExecutor.reset`` / ``set_residency`` — simply drops the
    staged copies and composes with the serving session's residency
    snapshot/rollback: a rolled-back group retries with an empty streamer
    and loads synchronously, keeping counters exact.

    Stall accounting is modelled, not measured: the caller stages the batch
    together with the cost model's residual
    (``GraphCostModel.prefetch_stall_seconds`` — load seconds minus the
    overlap window); :meth:`finish_group` returns that stall iff the group
    consumed any staged copy, and the engine adds it to the group's
    ``ExecutionStats.stream_stall_seconds``.

    The ``lax.scan`` fused path reads its stacked per-suffix parameter
    cache (``_stacked_suffix_params``) rather than per-node params, so
    committed copies are bypassed there — values are identical either way;
    only the unrolled/per-block paths physically consume the staged
    arrays.  Accounting is dispatch-mode independent regardless.
    """

    def __init__(self, executor: "TaskGraphExecutor"):
        self._executor = executor
        self._staged: Dict[NodeId, Any] = {}
        self._committed_since_stage = False
        #: Modelled stall (seconds) of the pending staged batch: the part of
        #: its load time that did not fit in the overlap window.
        self.pending_stall_seconds = 0.0
        # Lifetime telemetry (not part of ExecutionStats: these describe the
        # streamer mechanism, not the logical execution counters).
        self.prefetches = 0
        self.staged_bytes = 0.0
        self.committed_bytes = 0.0
        self.cancels = 0

    def staged_nodes(self) -> FrozenSet[NodeId]:
        """Nodes with a staged (uncommitted) copy in flight."""
        return frozenset(self._staged)

    def stage(
        self,
        loads: Sequence[Tuple[int, NodeId]],
        stall_seconds: float = 0.0,
    ) -> None:
        """Issue async copies for ``loads`` (``GraphCostModel.plan_loads``
        entries), replacing any previously staged batch."""
        self.cancel()
        ex = self._executor
        for depth, node in loads:
            params = ex.program.node_params[node]
            if ex.mesh is not None:
                copy = jax.tree_util.tree_map(ex._place_param_leaf, params)
            else:
                copy = jax.tree_util.tree_map(jax.device_put, params)
            self._staged[node] = copy
            self.staged_bytes += ex.program.block_costs[depth].weight_bytes
        if loads:
            self.prefetches += 1
            self.pending_stall_seconds = float(stall_seconds)

    def commit(self, node: NodeId) -> bool:
        """Adopt ``node``'s staged copy as its parameters, if one exists.

        Called exactly where the executor accounts a weight load; ``True``
        means the load's bytes arrived via the prefetch stream (the caller
        counts them in ``ExecutionStats.prefetched_bytes``).
        """
        copy = self._staged.pop(node, None)
        if copy is None:
            return False
        ex = self._executor
        if ex.mesh is not None:
            ex._placed_node[node] = copy
        else:
            ex._streamed_node[node] = copy
        self._committed_since_stage = True
        self.committed_bytes += ex.program.block_costs[node[0]].weight_bytes
        return True

    def finish_group(self) -> float:
        """Close out the staged batch after its group ran.

        Returns the batch's modelled stall when the group committed any of
        it (the stream was on this group's critical path), else ``0.0``;
        uncommitted leftovers (e.g. gated-off tasks) are dropped — the next
        prefetch re-plans from actual residency.
        """
        stall = (
            self.pending_stall_seconds if self._committed_since_stage else 0.0
        )
        self._staged.clear()
        self.pending_stall_seconds = 0.0
        self._committed_since_stage = False
        return stall

    def cancel(self) -> None:
        """Drop the staged (uncommitted) batch and its pending stall."""
        if self._staged or self.pending_stall_seconds:
            self.cancels += 1
        self._staged.clear()
        self.pending_stall_seconds = 0.0
        self._committed_since_stage = False

    def invalidate(self) -> None:
        """Cancel staging *and* drop committed single-device copies.

        The residency boundary hook (``reset`` / ``set_residency``): after
        a rollback or cold reset no streamed state — staged or already
        committed — may outlive the residency it was planned against.
        """
        self.cancel()
        self._executor._streamed_node.clear()


class TaskGraphExecutor:
    """Stateful executor with block residency + activation caching.

    Args:
      program: the bound multitask program.
      jit_blocks: jit-compile the dispatched programs (fused suffixes, or the
        per-block reference path).
      fused: execute each non-shared suffix as one fused program (default);
        ``False`` selects the per-block reference dispatch path.
      mesh: optional ``jax.sharding.Mesh`` for sharded execution: the batch
        dimension shards over the policy's batch axes, parameters over the
        policy's ``model``/``fsdp`` axes (``ShardingPolicy.param_spec``),
        and activations are constrained to the batch layout inside every
        fused program — so the compiled suffix is identical to what the
        collective calibration lowers.  Requires the fused jitted path.
      sharding: logical->physical axis policy; defaults to ``TP_POLICY``
        when a mesh is given.
      gater: optional :class:`~repro.adaptive.gating.BlockGater` making
        execution input-conditional: shape-preserving blocks of every
        dispatched suffix run only for the batch rows whose confidence is
        still below the gater's threshold, skipped rows pass their
        activation through unchanged, and the realized per-(block, row)
        fire counts land in ``ExecutionStats`` (``block_rows_fired`` /
        ``flops_gated``) and :attr:`last_gate_record`.  Gating is masked
        *inside* the compiled programs (``jnp.where`` on the scan carry),
        so jit keys stay ``(task, resume, shape)`` — thresholds enter as a
        runtime array and never retrace.
    """

    def __init__(
        self,
        program: MultitaskProgram,
        jit_blocks: bool = True,
        fused: bool = True,
        mesh: Optional[Any] = None,
        sharding: Optional[ShardingPolicy] = None,
        gater: Optional[Any] = None,
    ):
        self.program = program
        self._jit = jit_blocks
        self._fused = fused
        self.gater = gater
        if mesh is not None and not (jit_blocks and fused):
            raise ValueError(
                "mesh-sharded execution requires the fused jitted dispatch "
                "path (jit_blocks=True, fused=True)"
            )
        self.mesh = mesh
        self.sharding: Optional[ShardingPolicy] = (
            sharding if sharding is not None
            else (TP_POLICY if mesh is not None else None)
        )
        self._compiled: Dict[int, Callable] = {}
        self._compiled_heads: Dict[int, Callable] = {}
        self._compiled_batch: Dict[int, Callable] = {}
        self._compiled_heads_batch: Dict[int, Callable] = {}
        # (task, resume, batched, x_shape, x_dtype) -> (callable, mode); mode
        # is "scan" (stacked params + lax.scan) or "unrolled".
        self._compiled_fused: Dict[Tuple, Tuple[Callable, str]] = {}
        # (task, start, stop, batched, x_shape, x_dtype) -> (callable, mode):
        # headless segment programs for checkpointed (intermittent) suffixes.
        self._compiled_segment: Dict[Tuple, Tuple[Callable, str]] = {}
        # (task, resume) -> stacked suffix params for the scan mode.
        self._stacked_params: Dict[Tuple[int, int], Any] = {}
        # (task, start, stop) -> stacked segment params for the scan mode.
        self._stacked_seg_params: Dict[Tuple[int, int, int], Any] = {}
        # Mesh-placed parameter copies (input-independent; survive reset).
        self._placed_node: Dict[NodeId, Any] = {}
        self._placed_head: Dict[int, Any] = {}
        # Streamed-and-committed single-device parameter copies (the mesh
        # path commits into _placed_node instead); value-identical to
        # program.node_params, dropped at every residency boundary.
        self._streamed_node: Dict[NodeId, Any] = {}
        # Double-buffered host->device weight prefetcher (serving engines
        # drive it when EnginePolicy.streaming is on; idle otherwise).
        self.streamer = WeightStreamer(self)
        # Calibration caches: suffix-input avals, lowered HLO text, and the
        # per-kind collective bytes the cost model adds per dispatch.
        self._suffix_sds: Dict[Tuple, jax.ShapeDtypeStruct] = {}
        self._suffix_hlo: Dict[Tuple, str] = {}
        self._coll_bytes: Dict[Tuple, Dict[str, float]] = {}
        # Physical program dispatches (jitted-call invocations).  Cumulative;
        # not part of ExecutionStats (those are cost-model-predictable logical
        # counters — dispatches depend on the fused/per-block mode).
        self.dispatch_count = 0
        # Batch rows of every batched task run (one dispatch on the fused
        # path), and how many of them were padding: physical counts, like
        # ``dispatch_count``, and not part of ExecutionStats.
        self.rows_dispatched = 0
        self.rows_padded = 0
        # Adaptive-gating readback: per-dispatch realized fire masks of the
        # current task (``(start_depth, bool array)`` fragments, one per
        # dispatched segment), the finished task's TaskGateRecord, and the
        # per-task trace of the last run/run_batch call.
        self._fired_frags: List[Tuple[int, Any]] = []
        self.last_gate_record: Optional[TaskGateRecord] = None
        self.last_trace: List[TaskGateRecord] = []
        self.reset()

    def _gate_key(self) -> Optional[Tuple]:
        """Compile-cache discriminator for the active gater.

        Joins every program/calibration cache key so toggling or swapping
        the gater (different mode or confidence fn) never hits a program
        traced for other gate semantics.  Threshold changes do NOT change
        the key — thresholds are runtime inputs.
        """
        if self.gater is None:
            return None
        return (self.gater.mode, self.gater.confidence_fn)

    @property
    def fused(self) -> bool:
        """Whether suffixes dispatch as single fused programs (vs. the
        per-block reference path).  Settable at any point between tasks —
        both paths produce identical counters and (allclose-)identical
        outputs, so flipping it never changes accounting or results; the
        serving session's degradation ladder uses this to re-run a failed
        fused dispatch through the reference path.  Mesh-sharded executors
        require the fused path and reject ``False``.
        """
        return self._fused

    @fused.setter
    def fused(self, value: bool) -> None:
        if not value and self.mesh is not None:
            raise ValueError(
                "mesh-sharded execution requires the fused dispatch path; "
                "cannot set fused=False on a mesh executor"
            )
        self._fused = bool(value)

    # ---------------------------------------------------------------- state
    def reset(self) -> None:
        """Cold state: nothing resident, nothing cached, nothing streamed."""
        depth = self.program.graph.depth
        self._resident: List[Optional[NodeId]] = [None] * depth
        self.streamer.invalidate()
        self.clear_activations()

    def clear_activations(self) -> None:
        """Drop cached activations but keep weight residency (warm start).

        Weights are input-independent, activations are not: the whole-order
        entry points (:meth:`run` / :meth:`run_batch`) call this on entry so
        a new input can never resume from a previous input's activations,
        while the resident blocks remain loaded.  This is the warm-start
        boundary the serving engine uses between request groups.  Callers
        driving :meth:`run_task` / :meth:`run_task_batch` directly own this
        contract themselves.
        """
        depth = self.program.graph.depth
        self._activations: List[Optional[jnp.ndarray]] = [None] * depth
        self._act_owner: List[Optional[NodeId]] = [None] * depth
        self._act_shape: Optional[Tuple[int, ...]] = None

    def residency_state(self) -> ResidencyState:
        """Per-depth resident blocks, for warm-start cost accounting.

        Feed this to ``GraphCostModel.predicted_stats(..., resume=state)``
        (or ``predicted_group_stats``) to predict exactly what a warm
        continuation will load versus skip.
        """
        return tuple(self._resident)

    def set_residency(self, state: Sequence[Optional[NodeId]]) -> None:
        """Restore a residency snapshot (rollback / replay helper).

        Only weight residency is restored; activations are always cleared —
        they belong to a specific input, which a snapshot does not carry.
        Any in-flight prefetch is cancelled and committed streamed copies
        dropped (:meth:`WeightStreamer.invalidate`): a snapshot restore is
        the crash-recovery rollback boundary, after which no streamed state
        planned against the pre-rollback residency may survive — the next
        attempt loads synchronously and stays counter-exact.
        """
        depth = self.program.graph.depth
        if len(state) != depth:
            raise ValueError(
                f"residency state has {len(state)} slots, expected {depth}"
            )
        self._resident = list(state)
        self.streamer.invalidate()
        self.clear_activations()

    def activation_checkpoint(
        self, task: int
    ) -> Optional["ActivationCheckpoint"]:
        """Snapshot the deepest cached activation along ``task``'s path.

        This is what the serving journal persists at a segmented suffix's
        commit points: one ``(depth, node, value)`` triple is enough to
        resume the interrupted suffix, because the task graph is a tree —
        the node identity pins the whole prefix chain that produced the
        value.  Returns ``None`` when nothing on the path is cached.
        """
        path = self.program.graph.path(task)
        best: Optional[int] = None
        for d, node in enumerate(path):
            if self._act_owner[d] == node and self._activations[d] is not None:
                best = d
        if best is None:
            return None
        return ActivationCheckpoint(
            depth=best,
            node=path[best],
            value=self._activations[best],
            act_shape=self._act_shape,
        )

    def restore_activation(self, ckpt: "ActivationCheckpoint") -> None:
        """Re-seed the activation cache from a journaled crash checkpoint.

        All other activation slots are cleared (they did not survive the
        power failure); the next task sharing the checkpoint's node resumes
        from ``ckpt.depth + 1`` instead of 0.  Call *after*
        :meth:`set_residency` — restoring residency clears activations.
        """
        self.clear_activations()
        self._activations[ckpt.depth] = jnp.asarray(ckpt.value)
        self._act_owner[ckpt.depth] = ckpt.node
        self._act_shape = (
            tuple(ckpt.act_shape) if ckpt.act_shape is not None else None
        )

    def _guard_act_shape(self, shape: Tuple[int, ...]) -> None:
        """Invalidate cached activations produced for a different input shape
        (e.g. switching between the single-request and batched paths)."""
        if self._act_shape is not None and self._act_shape != shape:
            self.clear_activations()
        self._act_shape = shape

    # ------------------------------------------------- per-block (reference)
    def _block_fn(self, depth: int) -> Callable:
        if depth not in self._compiled:
            fn = self.program.block_fns[depth]
            self._compiled[depth] = jax.jit(fn) if self._jit else fn
        return self._compiled[depth]

    def _head_fn(self, task: int) -> Callable:
        if task not in self._compiled_heads:
            fn = self.program.head_fns[task]
            self._compiled_heads[task] = jax.jit(fn) if self._jit else fn
        return self._compiled_heads[task]

    def _block_fn_batch(self, depth: int) -> Callable:
        # vmap over the stacked request axis; params are shared across the
        # batch.  jit's shape-keyed cache yields one compile per
        # (depth, batch-shape) — exactly the recompilation budget the
        # request-group scheduler's padded shapes bound.
        if depth not in self._compiled_batch:
            fn = jax.vmap(self.program.block_fns[depth], in_axes=(None, 0))
            self._compiled_batch[depth] = jax.jit(fn) if self._jit else fn
        return self._compiled_batch[depth]

    def _head_fn_batch(self, task: int) -> Callable:
        if task not in self._compiled_heads_batch:
            fn = jax.vmap(self.program.head_fns[task], in_axes=(None, 0))
            self._compiled_heads_batch[task] = jax.jit(fn) if self._jit else fn
        return self._compiled_heads_batch[task]

    # ------------------------------------------------------ mesh placement
    def _place_param_leaf(self, leaf: Any, stacked: bool = False) -> Any:
        """``device_put`` one parameter leaf to its policy layout."""
        shape = tuple(jnp.shape(leaf))
        spec = self.sharding.param_spec(shape[1:] if stacked else shape)
        if stacked:
            spec = P(None, *spec)  # the scan's layer axis never shards
        spec = fit_spec(shape, spec, self.mesh)
        return jax.device_put(leaf, NamedSharding(self.mesh, spec))

    def _node_param(self, node: NodeId) -> Any:
        if self.mesh is None:
            streamed = self._streamed_node.get(node)
            if streamed is not None:
                return streamed
            return self.program.node_params[node]
        if node not in self._placed_node:
            self._placed_node[node] = jax.tree_util.tree_map(
                self._place_param_leaf, self.program.node_params[node]
            )
        return self._placed_node[node]

    def _head_param(self, task: int) -> Any:
        if self.mesh is None:
            return self.program.head_params[task]
        if task not in self._placed_head:
            self._placed_head[task] = jax.tree_util.tree_map(
                self._place_param_leaf, self.program.head_params[task]
            )
        return self._placed_head[task]

    def _batch_sharding(self, shape: Tuple[int, ...], batched: bool):
        """The NamedSharding of a batch-leading tensor (replicated when the
        tensor carries no batch axis, i.e. the single-request path)."""
        spec = P(self.sharding.physical("batch")) if batched else P()
        return NamedSharding(self.mesh, fit_spec(shape, spec, self.mesh))

    def _act_constrainer(self, batched: bool) -> Optional[Callable]:
        """Constraint pinning activations to the batch layout inside fused
        programs, so the executed program equals the calibrated one and
        cached activations never reshard on re-entry."""
        if self.mesh is None or not batched:
            return None

        def constrain(y: jnp.ndarray) -> jnp.ndarray:
            return jax.lax.with_sharding_constraint(
                y, self._batch_sharding(tuple(y.shape), batched=True)
            )

        return constrain

    # -------------------------------------------------------- fused suffix
    def _suffix_params(self, task: int, resume: int) -> Tuple[Any, ...]:
        path = self.program.graph.path(task)
        return tuple(
            self._node_param(path[d])
            for d in range(resume, self.program.graph.depth)
        )

    def _stacked_suffix_params(self, task: int, resume: int) -> Any:
        key = (task, resume)
        if key not in self._stacked_params:
            path = self.program.graph.path(task)
            params = tuple(
                self.program.node_params[path[d]]
                for d in range(resume, self.program.graph.depth)
            )
            stacked = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *params
            )
            if self.mesh is not None:
                stacked = jax.tree_util.tree_map(
                    lambda l: self._place_param_leaf(l, stacked=True), stacked
                )
            self._stacked_params[key] = stacked
        return self._stacked_params[key]

    def _fused_fn(
        self,
        task: int,
        resume: int,
        batched: bool,
        shape: Tuple[int, ...],
        dtype: Any,
    ) -> Tuple[Callable, str]:
        """Build (or fetch) the fused suffix program for one resume point.

        The program runs blocks ``resume .. depth-1`` plus the task head in a
        single dispatch and returns ``(per-depth activations, head output)``
        — the intermediate activations feed the Python-level cache so later
        tasks can still resume mid-path.  Mode "scan" stacks the suffix's
        (homogeneous, shape-preserving) params and iterates with
        ``lax.scan``; mode "unrolled" traces the heterogeneous suffix block
        by block inside one program.  ``shape``/``dtype`` describe the
        suffix's input ``h``; on a mesh every activation (and the head
        output) is additionally constrained to the batch layout.

        With a gater the program takes an extra per-depth threshold array
        (runtime float32, scanned alongside the params) and returns a third
        output: the ``(L, B)`` (or ``(L,)`` unbatched) boolean fire masks.
        A gated-off row's activation passes through unchanged
        (``jnp.where`` on the carry); blocks that are not shape-preserving
        cannot pass rows through and always fire.
        """
        shape = tuple(shape)
        dtype = jnp.dtype(dtype)
        key = (task, resume, batched, shape, dtype, self._gate_key())
        if key in self._compiled_fused:
            return self._compiled_fused[key]

        graph = self.program.graph
        depth = graph.depth
        suffix = list(range(resume, depth))
        base_fns = [self.program.block_fns[d] for d in suffix]
        head = self.program.head_fns[task]
        if batched:
            fns = [jax.vmap(f, in_axes=(None, 0)) for f in base_fns]
            head = jax.vmap(head, in_axes=(None, 0))
        else:
            fns = list(base_fns)
        cst = self._act_constrainer(batched)

        mode = "unrolled"
        if len(suffix) >= 2 and all(f is base_fns[0] for f in base_fns):
            params = self._suffix_params(task, resume)
            specs = {_leaf_specs(p) for p in params}
            if len(specs) == 1:
                # Same fn + same param shapes; scan also needs the carry
                # shape to be invariant — verify without executing.  Only
                # abstract-evaluation incompatibilities mean "not
                # scannable": shape/dtype mismatches raise
                # TypeError/ValueError, and value-dependent block fns (legal
                # on the unjitted eager path) cannot trace abstractly at
                # all.  Anything else is a real bug in the block fn and must
                # surface, not silently demote the dispatch mode.
                try:
                    spec = jax.eval_shape(
                        fns[0],
                        params[0],
                        jax.ShapeDtypeStruct(shape, dtype),
                    )
                except (
                    TypeError, ValueError, jax.errors.ConcretizationTypeError
                ):
                    spec = None
                if (
                    spec is not None
                    and spec.shape == shape
                    and spec.dtype == dtype
                ):
                    mode = "scan"

        gater = self.gater
        if gater is not None:
            conf_fn = (
                jax.vmap(gater.confidence_fn) if batched
                else gater.confidence_fn
            )
            early = gater.mode == "early_exit"

        if mode == "scan":
            step_fn = fns[0]

            if gater is None:

                def fused(stacked, head_p, h):
                    def step(carry, p):
                        y = step_fn(p, carry)
                        if cst is not None:
                            y = cst(y)
                        return y, y

                    h_last, acts = jax.lax.scan(step, h, stacked)
                    out = head(head_p, h_last)
                    return acts, out if cst is None else cst(out)

            else:

                def fused(stacked, thrs, head_p, h):
                    alive0 = (
                        jnp.ones(h.shape[:1], bool) if batched
                        else jnp.asarray(True)
                    )

                    def step(carry, inp):
                        hh, alive = carry
                        p, thr = inp
                        fire = alive & (conf_fn(hh) < thr)
                        y = step_fn(p, hh)
                        y = jnp.where(_gate_bcast(fire, y), y, hh)
                        if cst is not None:
                            y = cst(y)
                        return (y, fire if early else alive), (y, fire)

                    (h_last, _), (acts, fired) = jax.lax.scan(
                        step, (h, alive0), (stacked, thrs)
                    )
                    out = head(head_p, h_last)
                    return acts, (out if cst is None else cst(out)), fired

        else:

            if gater is None:

                def fused(params_tuple, head_p, h):
                    acts = []
                    for f, p in zip(fns, params_tuple):
                        h = f(p, h)
                        if cst is not None:
                            h = cst(h)
                        acts.append(h)
                    out = head(head_p, h)
                    return tuple(acts), out if cst is None else cst(out)

            else:

                def fused(params_tuple, thrs, head_p, h):
                    alive = (
                        jnp.ones(h.shape[:1], bool) if batched
                        else jnp.asarray(True)
                    )
                    acts = []
                    fired = []
                    for i, (f, p) in enumerate(zip(fns, params_tuple)):
                        y = f(p, h)
                        if y.shape == h.shape and y.dtype == h.dtype:
                            fire = alive & (conf_fn(h) < thrs[i])
                            y = jnp.where(_gate_bcast(fire, y), y, h)
                            if early:
                                alive = fire
                        else:
                            # Shape-changing block: passthrough is
                            # impossible, so every row computes it.
                            fire = jnp.ones_like(alive)
                        if cst is not None:
                            y = cst(y)
                        acts.append(y)
                        fired.append(fire)
                        h = y
                    out = head(head_p, h)
                    stacked_fired = (
                        jnp.stack(fired) if fired
                        else jnp.zeros(
                            (0,) + (h.shape[:1] if batched else ()), bool
                        )
                    )
                    return (
                        tuple(acts),
                        out if cst is None else cst(out),
                        stacked_fired,
                    )

        compiled = (
            jax.jit(_named(fused, f"suffix_r{resume}", batched, shape))
            if self._jit else fused
        )
        self._compiled_fused[key] = (compiled, mode)
        return compiled, mode

    def _suffix_thresholds(self, resume: int, stop: int) -> jnp.ndarray:
        """The gater's per-depth thresholds for blocks ``resume .. stop-1``
        as the runtime float32 array the compiled programs consume."""
        return jnp.asarray(
            self.gater.suffix_thresholds(resume, stop), jnp.float32
        )

    def _run_suffix_fused(
        self, task: int, resume: int, h: jnp.ndarray, batched: bool
    ) -> jnp.ndarray:
        """One dispatch for the whole (suffix + head) of ``task``."""
        graph = self.program.graph
        fn, mode = self._fused_fn(
            task, resume, batched, tuple(h.shape), jnp.result_type(h)
        )
        if mode == "scan":
            params = self._stacked_suffix_params(task, resume)
        else:
            params = self._suffix_params(task, resume)
        if self.gater is not None:
            acts, out, fired = fn(
                params,
                self._suffix_thresholds(resume, graph.depth),
                self._head_param(task),
                h,
            )
            self._fired_frags.append((resume, fired))
        else:
            acts, out = fn(params, self._head_param(task), h)
        if mode == "scan":
            acts = [acts[i] for i in range(graph.depth - resume)]
        self.dispatch_count += 1
        path = graph.path(task)
        for a, d in zip(acts, range(resume, graph.depth)):
            self._activations[d] = a
            self._act_owner[d] = path[d]
        return out

    # ----------------------------------------------- segmented (checkpoint)
    def _segment_params(self, task: int, start: int, stop: int) -> Tuple[Any, ...]:
        path = self.program.graph.path(task)
        return tuple(self._node_param(path[d]) for d in range(start, stop))

    def _stacked_segment_params(self, task: int, start: int, stop: int) -> Any:
        key = (task, start, stop)
        if key not in self._stacked_seg_params:
            path = self.program.graph.path(task)
            params = tuple(
                self.program.node_params[path[d]] for d in range(start, stop)
            )
            stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params)
            if self.mesh is not None:
                stacked = jax.tree_util.tree_map(
                    lambda l: self._place_param_leaf(l, stacked=True), stacked
                )
            self._stacked_seg_params[key] = stacked
        return self._stacked_seg_params[key]

    def _segment_fn(
        self,
        task: int,
        start: int,
        stop: int,
        batched: bool,
        shape: Tuple[int, ...],
        dtype: Any,
    ) -> Tuple[Callable, str]:
        """Build (or fetch) a *headless* fused program for blocks
        ``start .. stop-1`` of ``task``'s path.

        The segmented variant of :meth:`_fused_fn`: a checkpointed suffix is
        cut at its commit depths, each cut dispatching one of these segment
        programs so the Python-level journal hook can run — and a power
        failure can strike — at the block-depth boundary between them.  Same
        mode selection as the full-suffix program: ``lax.scan`` over stacked
        homogeneous shape-preserving blocks, else unrolled in one program.
        Returns the per-depth activations only (the final segment of a
        checkpointed suffix still runs through :meth:`_fused_fn`, which owns
        the head).

        With a gater the segment, like the full-suffix program, takes the
        per-depth threshold array and returns ``(acts, fired)``.  Each
        segment re-derives its alive mask from scratch (``alive = ones``):
        for shape-preserving passthrough gating a skipped row's activation
        — hence its confidence, hence its gate decision — is unchanged at
        the boundary, so the re-derived mask equals the mask an uncut
        suffix would have carried.  That is also why crash recovery replays
        identical gate decisions deterministically.
        """
        shape = tuple(shape)
        dtype = jnp.dtype(dtype)
        key = (task, start, stop, batched, shape, dtype, self._gate_key())
        if key in self._compiled_segment:
            return self._compiled_segment[key]

        segment = list(range(start, stop))
        base_fns = [self.program.block_fns[d] for d in segment]
        if batched:
            fns = [jax.vmap(f, in_axes=(None, 0)) for f in base_fns]
        else:
            fns = list(base_fns)
        cst = self._act_constrainer(batched)

        mode = "unrolled"
        if len(segment) >= 2 and all(f is base_fns[0] for f in base_fns):
            params = self._segment_params(task, start, stop)
            specs = {_leaf_specs(p) for p in params}
            if len(specs) == 1:
                try:
                    spec = jax.eval_shape(
                        fns[0], params[0], jax.ShapeDtypeStruct(shape, dtype)
                    )
                except (
                    TypeError, ValueError, jax.errors.ConcretizationTypeError
                ):
                    spec = None
                if (
                    spec is not None
                    and spec.shape == shape
                    and spec.dtype == dtype
                ):
                    mode = "scan"

        gater = self.gater
        if gater is not None:
            conf_fn = (
                jax.vmap(gater.confidence_fn) if batched
                else gater.confidence_fn
            )
            early = gater.mode == "early_exit"

        if mode == "scan":
            step_fn = fns[0]

            if gater is None:

                def seg(stacked, h):
                    def step(carry, p):
                        y = step_fn(p, carry)
                        if cst is not None:
                            y = cst(y)
                        return y, y

                    _h_last, acts = jax.lax.scan(step, h, stacked)
                    return acts

            else:

                def seg(stacked, thrs, h):
                    alive0 = (
                        jnp.ones(h.shape[:1], bool) if batched
                        else jnp.asarray(True)
                    )

                    def step(carry, inp):
                        hh, alive = carry
                        p, thr = inp
                        fire = alive & (conf_fn(hh) < thr)
                        y = step_fn(p, hh)
                        y = jnp.where(_gate_bcast(fire, y), y, hh)
                        if cst is not None:
                            y = cst(y)
                        return (y, fire if early else alive), (y, fire)

                    _last, (acts, fired) = jax.lax.scan(
                        step, (h, alive0), (stacked, thrs)
                    )
                    return acts, fired

        else:

            if gater is None:

                def seg(params_tuple, h):
                    acts = []
                    for f, p in zip(fns, params_tuple):
                        h = f(p, h)
                        if cst is not None:
                            h = cst(h)
                        acts.append(h)
                    return tuple(acts)

            else:

                def seg(params_tuple, thrs, h):
                    alive = (
                        jnp.ones(h.shape[:1], bool) if batched
                        else jnp.asarray(True)
                    )
                    acts = []
                    fired = []
                    for i, (f, p) in enumerate(zip(fns, params_tuple)):
                        y = f(p, h)
                        if y.shape == h.shape and y.dtype == h.dtype:
                            fire = alive & (conf_fn(h) < thrs[i])
                            y = jnp.where(_gate_bcast(fire, y), y, h)
                            if early:
                                alive = fire
                        else:
                            fire = jnp.ones_like(alive)
                        if cst is not None:
                            y = cst(y)
                        acts.append(y)
                        fired.append(fire)
                        h = y
                    stacked_fired = (
                        jnp.stack(fired) if fired
                        else jnp.zeros(
                            (0,) + (h.shape[:1] if batched else ()), bool
                        )
                    )
                    return tuple(acts), stacked_fired

        compiled = (
            jax.jit(_named(seg, f"segment_{start}_{stop}", batched, shape))
            if self._jit else seg
        )
        self._compiled_segment[key] = (compiled, mode)
        return compiled, mode

    def _run_suffix_segmented(
        self,
        task: int,
        resume: int,
        h: jnp.ndarray,
        batched: bool,
        checkpoint_depths: Sequence[int],
        checkpoint_hook: Optional[Callable[[int], None]],
    ) -> jnp.ndarray:
        """Checkpointed suffix: commit points at block-depth boundaries.

        Each checkpoint depth ``d`` in ``[resume, depth-1)`` ends a segment
        dispatch after block ``d``; the hook then fires with the activation
        for depth ``d`` freshly cached — the journal write, and the point a
        :class:`~repro.serving.reliability.PowerFailureInjector` kills the
        session.  The remainder past the last cut runs through the ordinary
        fused program (:meth:`_run_suffix_fused`), so an uncut suffix is
        byte-identical to the non-intermittent path.  Counters never change
        — segmentation only adds dispatches (and the hook's own checkpoint
        accounting).
        """
        graph = self.program.graph
        path = graph.path(task)
        cur = resume
        for d in sorted(set(checkpoint_depths)):
            if d < cur or d >= graph.depth - 1:
                continue  # already covered, or past the last cut point
            fn, mode = self._segment_fn(
                task, cur, d + 1, batched, tuple(h.shape), jnp.result_type(h)
            )
            if mode == "scan":
                params = self._stacked_segment_params(task, cur, d + 1)
            else:
                params = self._segment_params(task, cur, d + 1)
            if self.gater is not None:
                acts, fired = fn(
                    params, self._suffix_thresholds(cur, d + 1), h
                )
                self._fired_frags.append((cur, fired))
            else:
                acts = fn(params, h)
            if mode == "scan":
                acts = [acts[i] for i in range(d + 1 - cur)]
            self.dispatch_count += 1
            for a, dd in zip(acts, range(cur, d + 1)):
                self._activations[dd] = a
                self._act_owner[dd] = path[dd]
            h = self._activations[d]
            if checkpoint_hook is not None:
                checkpoint_hook(d)
            cur = d + 1
        return self._run_suffix_fused(task, cur, h, batched)

    def _run_suffix_blocks(
        self,
        task: int,
        resume: int,
        h: jnp.ndarray,
        batched: bool,
        checkpoint_depths: Sequence[int] = (),
        checkpoint_hook: Optional[Callable[[int], None]] = None,
    ) -> jnp.ndarray:
        """Reference path: one dispatch per block plus one for the head.

        Checkpoint hooks fire at the same block-depth boundaries as the
        segmented fused path, so the degradation ladder's unfused rung keeps
        journaling (and checkpoint accounting) identical.
        """
        graph = self.program.graph
        path = graph.path(task)
        cuts = {
            d for d in checkpoint_depths if resume <= d < graph.depth - 1
        }
        block_fn = self._block_fn_batch if batched else self._block_fn
        head_fn = self._head_fn_batch if batched else self._head_fn
        gater = self.gater
        if gater is not None:
            conf_fn = (
                jax.vmap(gater.confidence_fn) if batched
                else gater.confidence_fn
            )
            thrs = gater.suffix_thresholds(resume, graph.depth)
            alive = (
                jnp.ones(h.shape[:1], bool) if batched else jnp.asarray(True)
            )
            fired: List[jnp.ndarray] = []
        for d in range(resume, graph.depth):
            node = path[d]
            y = block_fn(d)(self._node_param(node), h)
            if gater is not None:
                if y.shape == h.shape and y.dtype == h.dtype:
                    fire = alive & (conf_fn(h) < thrs[d - resume])
                    y = jnp.where(_gate_bcast(fire, y), y, h)
                    if gater.mode == "early_exit":
                        alive = fire
                else:
                    fire = jnp.ones_like(alive)
                fired.append(fire)
            h = y
            self.dispatch_count += 1
            self._activations[d] = h
            self._act_owner[d] = node
            if d in cuts and checkpoint_hook is not None:
                checkpoint_hook(d)
        if gater is not None:
            stacked_fired = (
                jnp.stack(fired) if fired
                else jnp.zeros((0,) + (h.shape[:1] if batched else ()), bool)
            )
            self._fired_frags.append((resume, stacked_fired))
        out = head_fn(task)(self._head_param(task), h)
        self.dispatch_count += 1
        return out

    # ------------------------------------------------------------------ run
    def _run_task_impl(
        self,
        task: int,
        x: jnp.ndarray,
        stats: ExecutionStats,
        weight: int,
        batched: bool,
        checkpoint_depths: Sequence[int] = (),
        checkpoint_hook: Optional[Callable[[int], None]] = None,
        row_mask: Optional[Any] = None,
    ) -> jnp.ndarray:
        """Shared body of the single-request and batched task execution.

        The residency/resume/accounting invariants live ONLY here so the two
        paths cannot drift: ``weight`` is the logical request multiplicity
        scaling the per-request counters (flops/tasks), while load counters
        stay physical (once per invocation).  Accounting is dispatch-mode
        independent: the fused and per-block paths produce identical stats.

        With a gater the per-block flop accounting is deferred until after
        the dispatch: the realized fire masks are read back and each
        executed block's flops split into ``flops_executed`` (rows that
        fired) and ``flops_gated`` (rows whose gate skipped it).  Loads stay
        physical and ungated — the scan program consumes every stacked
        block's params regardless of who fires, so gating saves modelled
        FLOPs, not weight traffic.  ``row_mask`` (batched only) marks which
        rows of ``x`` are logically live — exactly ``weight`` of them; rows
        outside the mask (padding, or rows a legacy per-request gate turned
        off) execute physically but never count.
        """
        graph = self.program.graph
        path = graph.path(task)
        self._guard_act_shape(tuple(x.shape))
        self._fired_frags = []

        # Deepest block of this task's path whose activation is cached.  The
        # task graph is a tree, so an owner match at depth ``d`` pins the
        # whole chain above it — contiguity below is not required, which is
        # what lets a single restored crash checkpoint
        # (:meth:`restore_activation`) seed a mid-path resume.
        resume = 0
        for d, node in enumerate(path):
            if self._act_owner[d] == node and self._activations[d] is not None:
                resume = d + 1

        gated = self.gater is not None
        executed_costs: List[BlockCost] = []
        for d in range(graph.depth):
            node = path[d]
            bc = self.program.block_costs[d]
            if d < resume:
                # Shared prefix: weights resident AND activation cached ->
                # skip both the load and the execute.
                stats.blocks_skipped += 1
                stats.weight_bytes_skipped += bc.weight_bytes
                stats.flops_skipped += weight * bc.flops
                continue
            if self._resident[d] != node:
                if self.streamer.commit(node):
                    # The bytes still count as loaded — they moved — but
                    # arrived over the prefetch stream, overlapped with the
                    # previous group's compute.
                    stats.prefetched_bytes += bc.weight_bytes
                stats.weight_bytes_loaded += bc.weight_bytes
                self._resident[d] = node
            else:
                # Still resident (warm start across groups, or an intra-order
                # revisit): the load is skipped but the block must execute —
                # its input activation belongs to the current input.
                stats.weight_bytes_skipped += bc.weight_bytes
            stats.blocks_executed += 1
            if gated:
                executed_costs.append(bc)
            else:
                stats.flops_executed += weight * bc.flops
        stats.tasks_run += weight

        h = self._activations[resume - 1] if resume > 0 else x
        if self.mesh is not None:
            # Commit the suffix input to the batch layout (a no-op for
            # cached activations, which the fused program already constrains)
            # and account this dispatch's calibrated collective traffic —
            # physical, once per dispatch, like the load counters.
            h = jax.device_put(
                h, self._batch_sharding(tuple(h.shape), batched)
            )
            stats.add_collectives(self.suffix_collective_bytes(
                task, resume, tuple(h.shape), jnp.result_type(h), batched
            ))
        if self._fused:
            if checkpoint_depths:
                out = self._run_suffix_segmented(
                    task, resume, h, batched,
                    checkpoint_depths, checkpoint_hook,
                )
            else:
                out = self._run_suffix_fused(task, resume, h, batched)
        else:
            out = self._run_suffix_blocks(
                task, resume, h, batched, checkpoint_depths, checkpoint_hook
            )

        if gated:
            fired_rows = self._collect_fired(weight, batched, row_mask)
            if len(fired_rows) != len(executed_costs):
                raise AssertionError(
                    f"gate readback covered {len(fired_rows)} blocks, "
                    f"expected {len(executed_costs)}"
                )
            for bc, f in zip(executed_costs, fired_rows):
                stats.flops_executed += f * bc.flops
                stats.flops_gated += (weight - f) * bc.flops
                stats.block_rows_fired += f
                stats.block_rows_gated += weight - f
            self.last_gate_record = TaskGateRecord(
                task=task, weight=weight, fired=tuple(fired_rows),
                resume=resume,
            )
        else:
            self.last_gate_record = TaskGateRecord(
                task=task, weight=weight, resume=resume
            )
        return out

    def _collect_fired(
        self, weight: int, batched: bool, row_mask: Optional[Any]
    ) -> List[int]:
        """Per executed block depth, how many live rows fired.

        Reads back the dispatches' boolean fire masks (a device sync — the
        price of realized-count accounting) and reduces them over the
        logically-live rows: ``row_mask`` when given, else the first
        ``weight`` rows (the scheduler pads at the tail), else the whole
        single request.
        """
        mask = None if row_mask is None else np.asarray(row_mask, bool)
        counts: List[int] = []
        for _start, frag in self._fired_frags:
            arr = np.asarray(frag)
            if arr.shape[0] == 0:
                continue
            if not batched:
                counts.extend(int(bool(v)) * weight for v in arr)
            elif mask is not None:
                counts.extend(
                    int(np.count_nonzero(row & mask)) for row in arr
                )
            else:
                counts.extend(
                    int(np.count_nonzero(row[:weight])) for row in arr
                )
        return counts

    def run_task(
        self, task: int, x: jnp.ndarray, stats: ExecutionStats
    ) -> jnp.ndarray:
        """Run one task, resuming from the deepest cached shared block."""
        return self._run_task_impl(task, x, stats, 1, batched=False)

    def run(
        self,
        x: jnp.ndarray,
        order: Sequence[int],
        gate: Optional[Callable[[int, Dict[int, jnp.ndarray]], bool]] = None,
    ) -> Tuple[Dict[int, jnp.ndarray], ExecutionStats]:
        """Execute all tasks in ``order`` on input ``x``.

        Args:
          x: the shared input sample/batch (all tasks consume the same
            domain ``X`` in the paper).
          order: task permutation from the ordering solver.
          gate: optional runtime gate implementing conditional constraints —
            ``gate(task, results_so_far) -> bool``; a gated-off task is
            skipped entirely.

        Returns:
          (per-task outputs, execution stats).
        """
        self.clear_activations()  # never resume from a previous input
        results: Dict[int, jnp.ndarray] = {}
        stats = ExecutionStats()
        self.last_trace = []
        for t in order:
            if gate is not None and not gate(t, results):
                stats.tasks_skipped += 1
                self.last_trace.append(TaskGateRecord(task=t, weight=0))
                continue
            results[t] = self.run_task(t, x, stats)
            self.last_trace.append(self.last_gate_record)
        return results, stats

    # ---------------------------------------------------------------- batch
    def run_task_batch(
        self,
        task: int,
        xs: jnp.ndarray,
        stats: ExecutionStats,
        weight: Optional[int] = None,
        checkpoint_depths: Sequence[int] = (),
        checkpoint_hook: Optional[Callable[[int], None]] = None,
        row_mask: Optional[Any] = None,
        valid: Optional[int] = None,
        group_id: Optional[int] = None,
    ) -> jnp.ndarray:
        """Run one task for a stacked request group ``xs``: ``(B, *sample)``.

        Blocks are vmapped over the leading request axis while the Python
        residency/activation cache logic is shared across the whole group:
        every block on the path is loaded (and its batched activation cached)
        **once per group**, so weight loads amortise over ``B`` requests —
        the batch dimension the roadmap calls the main serving lever.

        Counters keep the cost model's per-request ("logical") accounting:
        ``weight`` is the number of real requests this execution serves
        (defaults to ``B``; the engine passes the gate-fired count, the
        scheduler the unpadded count).  Flop/task counters scale by
        ``weight``; load counters stay physical (once per group) — that gap
        *is* the block-loads-saved of batching.

        ``checkpoint_depths`` / ``checkpoint_hook`` select the segmented
        (intermittent) dispatch: the suffix is cut at those block-depth
        boundaries and the hook fires after each cut with the activation
        freshly cached — see :meth:`_run_suffix_segmented`.

        ``row_mask`` (optional ``(B,)`` bool) marks which rows are logically
        live for adaptive fire accounting — exactly ``weight`` of them; see
        :meth:`_run_task_impl`.

        ``valid`` (default ``B``) is how many leading rows are requests, the
        rest being padding: it feeds :attr:`rows_padded`.  ``group_id``, the
        serving session's group id, labels the ``repro.dispatch`` span.
        """
        rows = int(xs.shape[0])
        w = rows if weight is None else int(weight)
        with span("dispatch", group=group_id, task=task, rows=rows) as run:
            out = self._run_task_impl(
                task, xs, stats, w, batched=True,
                checkpoint_depths=checkpoint_depths,
                checkpoint_hook=checkpoint_hook,
                row_mask=row_mask,
            )
            run.set_metadata(resume=self.last_gate_record.resume)
        self.rows_dispatched += rows
        self.rows_padded += rows - (rows if valid is None else int(valid))
        return out

    def run_batch(
        self,
        xs: jnp.ndarray,
        order: Sequence[int],
        gate: Optional[Callable[[int, Dict[int, jnp.ndarray]], bool]] = None,
        valid: Optional[int] = None,
    ) -> Tuple[Dict[int, jnp.ndarray], ExecutionStats]:
        """Execute all tasks in ``order`` once for a stacked request group.

        Args:
          xs: ``(B, *sample_shape)`` stacked inputs, one row per request
            (rows ``valid:`` may be padding added by the scheduler).
          order: task permutation from the ordering solver.
          gate: optional group-wise gate, same signature as :meth:`run` but
            receiving *batched* results; a gated-off task is skipped for the
            whole group.  Per-request gating lives in the serving engine,
            which drives :meth:`run_task_batch` directly.
          valid: number of real (non-padding) leading rows used for logical
            per-request accounting; defaults to ``B``.

        Returns:
          (per-task batched outputs ``{task: (B, *out_shape)}``, stats).
          With a cold executor the stats equal
          ``GraphCostModel.predicted_stats(order, batch_size=valid)``
          exactly; warm (no ``reset`` since a previous group) they equal
          ``predicted_stats(order, batch_size=valid, resume=state)`` where
          ``state`` was :meth:`residency_state` before this call.
        """
        self.clear_activations()  # never resume from a previous input
        v = int(xs.shape[0]) if valid is None else int(valid)
        results: Dict[int, jnp.ndarray] = {}
        stats = ExecutionStats()
        self.last_trace = []
        for t in order:
            if gate is not None and not gate(t, results):
                stats.tasks_skipped += v
                self.last_trace.append(TaskGateRecord(task=t, weight=0))
                continue
            results[t] = self.run_task_batch(
                t, xs, stats, weight=v, valid=v)
            self.last_trace.append(self.last_gate_record)
        return results, stats

    # ------------------------------------------- collective calibration
    def _suffix_input_sds(
        self,
        task: int,
        resume: int,
        x_shape: Tuple[int, ...],
        dtype: Any,
        batched: bool,
    ) -> jax.ShapeDtypeStruct:
        """Aval of the fused suffix's input ``h`` given the group input.

        For ``resume > 0`` the suffix consumes the cached activation at
        depth ``resume - 1``; its shape is derived by abstractly evaluating
        blocks ``0 .. resume-1`` along the task's own path (a shared prefix
        runs the same depth fns, so the shapes match whichever task actually
        produced the cache).
        """
        key = (task, resume, tuple(x_shape), jnp.dtype(dtype), batched)
        if key not in self._suffix_sds:
            path = self.program.graph.path(task)
            sds = jax.ShapeDtypeStruct(tuple(x_shape), jnp.dtype(dtype))
            for d in range(resume):
                fn = self.program.block_fns[d]
                if batched:
                    fn = jax.vmap(fn, in_axes=(None, 0))
                sds = jax.eval_shape(fn, self.program.node_params[path[d]], sds)
            self._suffix_sds[key] = sds
        return self._suffix_sds[key]

    def _lowered_suffix_text(
        self,
        task: int,
        resume: int,
        shape: Tuple[int, ...],
        dtype: Any,
        batched: bool,
    ) -> str:
        """Post-optimization HLO of one fused suffix dispatch.

        Lowered from the same jitted program, the same placed parameters,
        and the same committed input layout execution uses, so the analyzed
        module is the program that runs.
        """
        if not (self._jit and self._fused):
            raise ValueError(
                "suffix HLO calibration requires the fused jitted dispatch "
                "path (jit_blocks=True, fused=True)"
            )
        shape, dtype = tuple(shape), jnp.dtype(dtype)
        key = (task, resume, batched, shape, dtype, self._gate_key())
        if key not in self._suffix_hlo:
            fn, mode = self._fused_fn(task, resume, batched, shape, dtype)
            params = (
                self._stacked_suffix_params(task, resume) if mode == "scan"
                else self._suffix_params(task, resume)
            )
            if self.mesh is not None:
                in_sds = jax.ShapeDtypeStruct(
                    shape, dtype,
                    sharding=self._batch_sharding(shape, batched),
                )
            else:
                in_sds = jax.ShapeDtypeStruct(shape, dtype)
            if self.gater is not None:
                thrs_sds = jax.ShapeDtypeStruct(
                    (self.program.graph.depth - resume,), jnp.float32
                )
                lowered = fn.lower(
                    params, thrs_sds, self._head_param(task), in_sds
                )
            else:
                lowered = fn.lower(params, self._head_param(task), in_sds)
            self._suffix_hlo[key] = lowered.compile().as_text()
        return self._suffix_hlo[key]

    def suffix_hlo(
        self, task: int, resume: int, xs: Any, batched: bool = True
    ) -> str:
        """HLO text of the dispatch running ``task`` from depth ``resume``
        for group input ``xs`` — the independent-measurement hook tests use
        to check predicted collective bytes against ``HloCostModel``."""
        sds = self._suffix_input_sds(
            task, resume, tuple(jnp.shape(xs)), jnp.result_type(xs), batched
        )
        return self._lowered_suffix_text(
            task, resume, sds.shape, sds.dtype, batched
        )

    def suffix_collective_bytes(
        self,
        task: int,
        resume: int,
        shape: Tuple[int, ...],
        dtype: Any,
        batched: bool = True,
    ) -> Dict[str, float]:
        """Calibrated per-kind collective bytes of one suffix dispatch.

        ``shape``/``dtype`` describe the suffix *input* (the activation at
        ``resume - 1``, or the group input when ``resume == 0``).  Cached per
        key, and the single source both the executor's counters and the cost
        model's predictions add from — which is what makes
        ``session.stats == session.predicted`` exact on a mesh.
        """
        shape, dtype = tuple(shape), jnp.dtype(dtype)
        key = (task, resume, batched, shape, dtype, self._gate_key())
        if key not in self._coll_bytes:
            from repro.launch.hlo_cost import collective_breakdown

            self._coll_bytes[key] = collective_breakdown(
                self._lowered_suffix_text(task, resume, shape, dtype, batched)
            )
        return self._coll_bytes[key]

    def collective_view(
        self, xs: Any, batched: bool = True
    ) -> Optional["CollectiveView"]:
        """A :class:`CollectiveView` bound to group input ``xs``, for
        ``GraphCostModel.predicted_stats(..., collectives=view)``; ``None``
        without a mesh (single-device programs have no collectives)."""
        if self.mesh is None:
            return None
        return CollectiveView(
            self, tuple(jnp.shape(xs)), jnp.result_type(xs), batched
        )


class CollectiveView:
    """Per-(task, resume) calibrated collective bytes for one batch shape.

    The ``CollectiveCosts`` implementation the cost model consumes: bound to
    a group's (padded) input aval, it resolves each ``(task, resume)`` to
    the suffix-input aval and returns the executor-cached HLO-calibrated
    breakdown — the exact dict execution adds.
    """

    def __init__(
        self,
        executor: TaskGraphExecutor,
        x_shape: Tuple[int, ...],
        dtype: Any,
        batched: bool = True,
    ):
        self._executor = executor
        self._x_shape = tuple(x_shape)
        self._dtype = jnp.dtype(dtype)
        self._batched = bool(batched)

    def breakdown(self, task: int, resume: int) -> Dict[str, float]:
        sds = self._executor._suffix_input_sds(
            task, resume, self._x_shape, self._dtype, self._batched
        )
        return self._executor.suffix_collective_bytes(
            task, resume, sds.shape, sds.dtype, self._batched
        )


class VanillaExecutor:
    """Baseline: independently-trained networks run back to back.

    No block is ever considered resident across tasks and no activation is
    reused — every task pays its full load + execute cost (the paper's
    "Vanilla" baseline).
    """

    def __init__(self, program: MultitaskProgram, jit_blocks: bool = True):
        self.program = program
        self._inner = TaskGraphExecutor(program, jit_blocks)

    def run(
        self,
        x: jnp.ndarray,
        order: Optional[Sequence[int]] = None,
        gate: Optional[Callable[[int, Dict[int, jnp.ndarray]], bool]] = None,
    ) -> Tuple[Dict[int, jnp.ndarray], ExecutionStats]:
        order = list(order) if order is not None else list(
            range(self.program.graph.num_tasks)
        )
        results: Dict[int, jnp.ndarray] = {}
        stats = ExecutionStats()
        for t in order:
            if gate is not None and not gate(t, results):
                stats.tasks_skipped += 1
                continue
            self._inner.reset()  # forget residency + caches between tasks
            results[t] = self._inner.run_task(t, x, stats)
        return results, stats


def run_in_order(
    program: MultitaskProgram,
    x: jnp.ndarray,
    order: Sequence[int],
    gate: Optional[Callable[[int, Dict[int, jnp.ndarray]], bool]] = None,
) -> Tuple[Dict[int, jnp.ndarray], ExecutionStats]:
    """One-shot convenience wrapper around :class:`TaskGraphExecutor`."""
    return TaskGraphExecutor(program).run(x, order, gate)
