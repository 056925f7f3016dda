"""Serving engines.

* :class:`MultitaskEngine` — the Antler runtime: a task graph + optimal
  order + the block-cached executor, serving batched requests that each want
  some subset of the task set.  Conditional constraints become runtime gates
  (a dependent task is skipped when its prerequisite's outcome says so),
  which is exactly the paper's audio deployment (presence detector gating
  the other four classifiers).
* :class:`LMServer` — prefill + greedy decode loop over a
  :class:`~repro.models.registry.ModelApi` with a batched KV cache; used by
  the decode-shape dry-runs and the serving example.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import (
    TYPE_CHECKING, Any, Callable, Dict, FrozenSet, List, Optional, Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.adaptive.gate_model import GateModel, GateModelCalibrator
from repro.adaptive.gating import BlockGater
from repro.adaptive.policy import AdaptivePolicy
from repro.core.constraints import Constraints
from repro.core.cost_model import CheckpointSite, GraphCostModel
from repro.core.executor import MultitaskProgram, TaskGraphExecutor
from repro.core.ordering import optimal_order, solve_suborder
from repro.core.spans import span
from repro.core.types import (
    ExecutionStats, HardwareModel, TPU_V5E, TaskGateRecord,
)
from repro.models.registry import ModelApi
from repro.serving.batching import (
    RequestGroup, RequestGroupScheduler, effective_order, normalize_subset,
)
from repro.serving.policies import EnginePolicy
from repro.sharding.policy import ShardingPolicy, TP_POLICY

if TYPE_CHECKING:  # session imports engine; keep the runtime import lazy
    from repro.serving.journal import Journal
    from repro.serving.policies import SchedulingPolicy
    from repro.serving.reliability import FaultInjector, PowerFailureInjector
    from repro.serving.session import ServingSession


@dataclasses.dataclass
class MultitaskRequest:
    """One inference request: an input, the tasks it wants, and its SLOs.

    The SLO fields are advisory metadata the *session* layer acts on; the
    engine's execution path ignores them (they never change what computes):

    ``deadline`` is an absolute time on the session's clock by which the
    request must have been admitted for planning — a pump finding it overdue
    fails its future with :class:`~repro.serving.reliability.DeadlineExceeded`
    instead of planning it.  ``priority`` orders load shedding (higher wins)
    when a bounded session queue overflows.  ``tenant`` labels the request
    for per-tenant quota and admission-wait accounting.
    """

    x: Any
    tasks: Optional[Sequence[int]] = None  # None = all tasks
    deadline: Optional[float] = None       # session-clock absolute seconds
    priority: int = 0                      # higher survives shedding longer
    tenant: Optional[str] = None           # quota / wait-accounting label


@dataclasses.dataclass
class MultitaskResponse:
    """Engine reply for one request.

    ``stats`` are the counters of the *execution group* the request was
    served in (``group_size`` requests share one batched pass, so loads
    amortise); each response in a group carries its **own**
    ``dataclasses.replace`` copy, so group-mates never share a mutable
    counter object.  ``predicted_seconds`` is this request's per-request
    share of the group's cost **as it actually ran** — for a warm group
    that means the warm-start counters (loads skipped through cross-group
    residency), not a cold estimate.  ``warm_weight_bytes_saved`` is the
    group's total weight bytes *not* loaded because of warmth alone — the
    cold-minus-warm modelled loads, separating the cross-group saving from
    the intra-order prefix sharing already counted in
    ``stats.weight_bytes_skipped``.

    ``order`` is the engine's *global* task order (solved once at startup);
    ``effective_order`` is the sequence the request's group **actually
    ran** — the global order filtered to the group's task subset, or the
    group's re-solved per-plan order when
    ``EnginePolicy.resolve_order_per_plan`` is on.  ``stats`` always
    describe the effective order's execution, so consumers correlating
    counters with a task sequence must read ``effective_order``, not
    ``order``.  With ``group_size == 1``, a cold engine, and an all-tasks
    request, everything reduces to the original single-request semantics
    (and ``effective_order == order``).
    """

    outputs: Dict[int, jax.Array]
    stats: ExecutionStats
    order: Tuple[int, ...]
    predicted_seconds: float
    group_size: int = 1
    warm_weight_bytes_saved: float = 0.0
    effective_order: Tuple[int, ...] = ()
    # Recovery provenance (set by the session's reliability layer):
    # ``retries`` = failed attempts before the one that produced this
    # response; ``degraded`` names the fallback-ladder rung that succeeded
    # ("unfused" = per-block reference dispatch, "single_device" = off-mesh
    # fallback executor), ``None`` for the primary path.
    retries: int = 0
    degraded: Optional[str] = None
    # True when this response was rebuilt from a durable journal commit by
    # ``ServingSession.recover`` instead of produced by a live execution —
    # the exactly-once path after a power failure.
    recovered: bool = False


@dataclasses.dataclass
class IntermittentContext:
    """Journaling context threaded through one group's execution.

    Built by the session (the journal's owner) per group: ``journal`` /
    ``group_id`` let the engine's checkpoint hook write durable mid-suffix
    activation records under the group's identity, and ``checkpointing``
    turns the segmented dispatch on or off (the restart-from-scratch
    comparator arm journals begins/commits but never cuts a suffix).
    """

    journal: "Journal"
    group_id: int
    checkpointing: bool = True


@dataclasses.dataclass
class GroupExecution:
    """One executed request group — the session's unit of completed work.

    ``outputs`` holds the per-slot (valid rows only) task outputs;
    ``stats`` the executed counters of this group alone; ``predicted`` the
    cost model's prediction for the same group computed from the executor's
    residency immediately before execution (the incremental form of
    ``predicted_group_stats`` — merging the per-group predictions of a
    schedule equals the one-shot prediction of the whole schedule).
    ``predicted`` is conditioned on ``gate_trace``, the realized per-task
    gate outcomes of the execution (legacy ``gate=`` skips and adaptive
    per-block fire counts), which is what keeps ``stats == predicted``
    field-exact even for gated/adaptive groups.  ``expected`` is the
    *a-priori* expected-counter prediction under the engine's
    :class:`~repro.adaptive.gate_model.GateModel` — computed before
    execution, without peeking at the trace — or ``None`` when the engine
    is not adaptive.
    """

    group: RequestGroup
    eff: Tuple[int, ...]
    outputs: List[Dict[int, jax.Array]]
    stats: ExecutionStats
    predicted: ExecutionStats
    warm_saved: float
    expected: Optional[ExecutionStats] = None
    gate_trace: Optional[List[TaskGateRecord]] = None


class MultitaskEngine:
    """Antler end-to-end: ordering solved once at startup, executor reused.

    ``gates``: {task: fn(outputs_so_far) -> bool} runtime conditions
    implementing conditional constraints.

    Everything schedule-shaped is configured through one
    :class:`~repro.serving.policies.EnginePolicy` value (``policy``):

    * ``policy.warm_start`` keeps the executor's weight residency across
      request groups (and across ``serve_batch`` calls): a group whose
      first task shares a prefix with the previous group's boundary task
      skips those loads entirely.  Activations are always invalidated at
      group boundaries — they belong to the previous group's inputs — so
      outputs are identical to cold-per-group serving.
    * ``policy.group_ordering`` sequences the planned groups by the cost
      model's warm boundary costs (``repro.serving.batching.order_groups``).
    * ``policy.resolve_order_per_plan`` re-solves each group's *internal*
      task order seeded with the residency the engine will have when the
      group runs (see :meth:`plan_groups`).
    * ``policy.scheduling`` is the admission policy sessions (and the
      one-shot wrappers' internal sessions) run under.
    * ``policy.adaptive`` turns on input-adaptive execution: the executor
      gains a per-row confidence gater (early exit / per-block gating
      inside the fused suffixes), the cost model an expected-counter gate
      model the order solvers optimize, and sessions a deadline-ladder
      threshold knob.  ``gate_deps`` (or conditional constraint edges)
      declare which outputs each legacy runtime gate reads, which makes
      per-plan order re-solving sound for gated engines.

    None of these change results, only how much gets loaded.  The
    ``warm_start`` / ``group_ordering`` / ``scheduler`` keyword arguments
    are retained as conveniences that override the corresponding
    ``EnginePolicy`` field.

    Long-lived serving goes through :meth:`session` (async admission,
    futures, planning overlapped with execution); ``serve`` /
    ``serve_batch`` are thin wrappers that run a one-shot session.
    """

    def __init__(
        self,
        program: MultitaskProgram,
        constraints: Optional[Constraints] = None,
        hw: HardwareModel = TPU_V5E,
        gates: Optional[Dict[int, Callable[[Dict[int, jax.Array]], bool]]] = None,
        gate_deps: Optional[Dict[int, Sequence[int]]] = None,
        order: Optional[Sequence[int]] = None,
        scheduler: Optional[RequestGroupScheduler] = None,
        warm_start: Optional[bool] = None,
        group_ordering: Optional[bool] = None,
        policy: Optional[EnginePolicy] = None,
        fault_injector: Optional["FaultInjector"] = None,
        power_injector: Optional["PowerFailureInjector"] = None,
    ):
        self.program = program
        self.hw = hw
        self.constraints = constraints
        self.gates = gates or {}
        policy = policy if policy is not None else EnginePolicy()
        overrides: Dict[str, Any] = {}
        if warm_start is not None:
            overrides["warm_start"] = bool(warm_start)
        if group_ordering is not None:
            overrides["group_ordering"] = bool(group_ordering)
        if scheduler is not None:
            overrides["scheduler"] = scheduler
        if overrides:
            policy = dataclasses.replace(policy, **overrides)
        if policy.scheduler is None:
            # Fold the default in so engine.policy alone reconstructs the
            # engine's full scheduling behavior.
            policy = dataclasses.replace(
                policy, scheduler=RequestGroupScheduler()
            )
        self.mesh = policy.mesh
        self.sharding: Optional[ShardingPolicy] = (
            policy.sharding if policy.sharding is not None
            else (TP_POLICY if self.mesh is not None else None)
        )
        self.data_shards = (
            self.sharding.data_shards(self.mesh) if self.sharding else 1
        )
        self.weight_shards = (
            self.sharding.weight_shards(self.mesh) if self.sharding else 1
        )
        if self.data_shards > 1 and any(
            s % self.data_shards for s in policy.scheduler.batch_shapes
        ):
            # Fold the mesh's per-shard multiple into the scheduler so every
            # padded group splits evenly over the batch axes.
            policy = dataclasses.replace(
                policy,
                scheduler=RequestGroupScheduler(
                    batch_shapes=policy.scheduler.batch_shapes,
                    shard_multiple=self.data_shards,
                ),
            )
        if policy.streaming and not policy.warm_start:
            raise ValueError(
                "EnginePolicy.streaming requires warm_start: a cold engine "
                "resets the executor before every group, which cancels any "
                "staged prefetch — nothing could ever stream"
            )
        self.policy = policy
        # -------------------------------------------- input-adaptive gating
        self.adaptive: Optional[AdaptivePolicy] = policy.adaptive
        self._gater: Optional[BlockGater] = None
        self._calibrator: Optional[GateModelCalibrator] = None
        if self.adaptive is not None:
            self._gater = BlockGater(
                confidence_fn=self.adaptive.confidence,
                mode=self.adaptive.mode,
                threshold=float(self.adaptive.threshold),
                min_blocks=self.adaptive.min_blocks,
            )
            if self.adaptive.calibrate_online:
                self._calibrator = GateModelCalibrator()
        # Which tasks each runtime gate reads: {gated_task: (input_tasks,)}.
        # Declared deps make gates safe under per-plan order re-solving (the
        # inputs become precedence edges of the re-solve).  When not given
        # explicitly, derived from the conditional constraint edges — the
        # paper's gates *are* conditional constraints acted on at runtime.
        self.gate_deps: Dict[int, Tuple[int, ...]] = {}
        if gate_deps is not None:
            self.gate_deps = {
                int(t): tuple(int(i) for i in deps)
                for t, deps in gate_deps.items()
            }
        elif constraints is not None and self.gates:
            for t in self.gates:
                deps = tuple(sorted(
                    i for (i, j, _p) in constraints.conditional if j == t
                ))
                if deps:
                    self.gate_deps[t] = deps
        self._plan_constraints = self._build_plan_constraints(
            program.graph.num_tasks, constraints
        )
        self.cost_model = GraphCostModel(
            program.graph, program.block_costs, hw,
            weight_shards=self.weight_shards,
            gate_model=(
                self.adaptive.gate_model if self.adaptive is not None else None
            ),
        )
        self._cost_matrix = self.cost_model.cost_matrix()
        # Lazy per-plan re-solve matrix (expected costs when a gate model or
        # conditional constraints exist); dirtied by online calibration.
        self._resolve_mat: Optional[np.ndarray] = None
        if order is None:
            # optimal_order applies the Eq.-8 conditional weighting itself,
            # so the matrix folds in only the *adaptive* gate model here —
            # folding the constraints' probabilities too would double-count.
            init_matrix = (
                self.cost_model.expected_cost_matrix()
                if self.cost_model.gate_model is not None
                else self._cost_matrix
            )
            res = optimal_order(init_matrix, constraints)
            order = res.order
        self.order = tuple(order)
        if constraints is not None and not constraints.is_valid_order(self.order):
            raise ValueError("supplied order violates the constraints")
        if (
            self._plan_constraints is not None
            and not self._plan_constraints.is_valid_order(self.order)
        ):
            raise ValueError(
                "gate_deps edges conflict with the engine's task order: a "
                "gate would read an output its order produces later"
            )
        self.executor = TaskGraphExecutor(
            program, mesh=self.mesh, sharding=self.sharding,
            gater=self._gater,
        )
        # Deterministic chaos hook (see repro.serving.reliability): when
        # set, ``check`` is called at the plan/load/dispatch boundaries and
        # may raise.  Mutable on purpose — the chaos harness arms and
        # disarms it around specific traces.
        self.fault_injector = fault_injector
        # Whole-session power-failure hook (intermittent computing; see
        # repro.serving.reliability.PowerFailureInjector).  Checked at the
        # "group" / "suffix" / "prefetch" sites; raises PowerFailure — a
        # BaseException the session's retry machinery never absorbs.  Like
        # the fault injector, mutable on purpose; unlike it, the instance
        # should live *outside* the session so its schedule survives the
        # reboots it causes.
        self.power_injector = power_injector
        # Lazily built off-mesh executor for the degradation ladder's
        # "single_device" rung (mesh engines only; see execute_group_fallback).
        self._fallback_executor: Optional[TaskGraphExecutor] = None
        # Cumulative counters of the most recent serve_batch call; with no
        # gates and the default greedy scheduling these equal
        # predicted_group_stats(plan_groups(requests)) computed before that
        # call (property-tested; non-greedy policies admit in rounds, each
        # planned separately — see plan_groups).
        self.last_batch_stats = ExecutionStats()

    # Schedule flags read through the policy so there is exactly one source
    # of truth for "how this engine schedules".
    @property
    def warm_start(self) -> bool:
        return self.policy.warm_start

    @property
    def group_ordering(self) -> bool:
        return self.policy.group_ordering

    @property
    def streaming(self) -> bool:
        return self.policy.streaming

    @property
    def scheduler(self) -> RequestGroupScheduler:
        return self.policy.scheduler

    def normalized_subset(
        self, tasks: Optional[Sequence[int]]
    ) -> Optional[FrozenSet[int]]:
        """A request's task subset in the scheduler's bucket-key form:
        ``None`` for all-tasks (explicit or implicit), a frozenset else —
        the same :func:`~repro.serving.batching.normalize_subset` the
        scheduler buckets by, so policies score the groups that will form."""
        return normalize_subset(tasks, self.program.graph.num_tasks)

    def session(
        self,
        policy: Optional["SchedulingPolicy"] = None,
        clock: Optional[Callable[[], float]] = None,
        **kwargs: Any,
    ) -> "ServingSession":
        """Open a :class:`~repro.serving.session.ServingSession` on this
        engine (``policy`` defaults to ``self.policy.scheduling``).  Extra
        keyword arguments — ``max_pending``, ``overload``, ``retry``, … —
        forward to the session constructor."""
        from repro.serving.session import ServingSession

        return ServingSession(self, policy=policy, clock=clock, **kwargs)

    # ------------------------------------------------------------- planning
    def _build_plan_constraints(
        self, num_tasks: int, constraints: Optional[Constraints]
    ) -> Optional[Constraints]:
        """Constraints for per-plan re-solving: the engine's own, plus one
        precedence edge per declared gate input so a re-solved order can
        never move a gated task ahead of an output its gate reads."""
        edges = {
            (i, t) for t, deps in self.gate_deps.items() for i in deps
        }
        base = constraints.precedence if constraints is not None else frozenset()
        if not (edges - set(base)):
            return constraints
        return Constraints.make(
            num_tasks,
            precedence=set(base) | edges,
            conditional=(
                constraints.conditional if constraints is not None else ()
            ),
        )

    def _planning_gate_model(self) -> Optional[GateModel]:
        """The gate model per-plan re-solves price costs with.

        ``solve_suborder`` rebuilds precedence-only constraints, so the
        conditional constraints' Eq.-8 execution probabilities would be
        dropped on the floor — fold them into the gate model's task
        probabilities instead.  A *calibrated* (adaptive) task probability
        wins over the constraints' prior where both exist: it is the same
        quantity, measured rather than assumed.
        """
        gm = self.cost_model.gate_model
        if self.constraints is None or not self.constraints.conditional:
            return gm
        cgm = GateModel.from_constraints(self.constraints)
        if gm is None:
            return cgm
        task_fire = dict(cgm.task_fire)
        task_fire.update(gm.task_fire)
        return GateModel(fire=dict(gm.fire), task_fire=task_fire)

    def _resolve_matrix(self) -> np.ndarray:
        """Switching-cost matrix for per-plan re-solving: expected costs
        when any probability surface exists (adaptive gate model and/or
        conditional constraints), the exact matrix otherwise.  Cached;
        online calibration dirties the cache."""
        if self._resolve_mat is None:
            gm = self._planning_gate_model()
            self._resolve_mat = (
                self.cost_model.expected_cost_matrix(gm)
                if gm is not None else self._cost_matrix
            )
        return self._resolve_mat

    def plan_groups(
        self, requests: Sequence[MultitaskRequest]
    ) -> List[RequestGroup]:
        """The group plan one admitted planning batch over ``requests`` runs.

        Deterministic, so callers can plan, predict (via
        :meth:`predicted_group_stats`), and then serve the same requests.
        Note the plan/predict/serve equality is per *planning batch*: under
        the default :class:`GreedyBatchPolicy` a one-shot serve admits the
        whole request list as one batch, so ``plan_groups(requests)`` is
        exactly what ``serve_batch(requests)`` executes — but a windowed or
        affinity ``policy.scheduling`` admits in several policy-chosen
        rounds, each planned separately, so predict each round's admitted
        requests (as sessions do internally) rather than the full list.
        With ``policy.resolve_order_per_plan`` on, each group's internal
        task order is re-solved here (after group sequencing) and recorded
        on ``RequestGroup.order``, so planning, prediction, and execution
        all see the same per-plan orders.
        """
        use_order = self.group_ordering
        groups = self.scheduler.plan(
            requests,
            num_tasks=self.program.graph.num_tasks,
            cost_model=self.cost_model if use_order else None,
            task_order=self.order if use_order else None,
            initial_resident=(
                self.executor.residency_state()
                if use_order and self.warm_start else None
            ),
        )
        if self.policy.resolve_order_per_plan and all(
            t in self.gate_deps for t in self.gates
        ):
            # Gates are order-sensitive (a gate reads the outputs produced
            # so far), so re-solving requires every gate's inputs to be
            # declared (gate_deps) — they become precedence edges of the
            # re-solve, which keeps each gate's inputs ahead of it in any
            # solved order.  Conditional-probability constraints and
            # adaptive gate models are handled by pricing the re-solve with
            # the *expected* cost matrix (see _resolve_matrix), so the
            # per-plan orders optimize the same probability-weighted
            # objective (Eq. 8) as the global solve.
            with span("order", groups=len(groups)):
                groups = self._resolve_plan_orders(groups)
        return groups

    def group_order(self, group: RequestGroup) -> Tuple[int, ...]:
        """The task sequence ``group`` executes: its re-solved per-plan
        order when one was recorded, else the global order filtered to the
        group's subset."""
        if group.order is not None:
            return tuple(group.order)
        return tuple(effective_order(self.order, group.tasks))

    def _resolve_plan_orders(
        self, groups: Sequence[RequestGroup]
    ) -> List[RequestGroup]:
        """Residency-aware per-plan task-order re-solving.

        The global order is solved once, cold, over the full task set; a
        group serving only a subset — warm from whatever ran before — can
        have a strictly cheaper internal order.  Walking the planned groups
        in execution sequence, each group's subset is re-solved
        (:func:`repro.core.ordering.solve_suborder`) over the engine's
        switching-cost matrix with a virtual start node whose edges are the
        residency-conditioned entry loads (``resume_load_cost``), then the
        simulated residency advances to what executing that order leaves
        behind.  Outputs are order-independent (every task's output depends
        only on its input and path), so this changes loads, never results.

        Deliberately runs *after* ``order_groups``: inter-group sequencing
        and intra-group re-solving are mutually dependent (the boundary
        TSP needs each group's entry/exit task, the re-solve needs the
        execution sequence to carry residency), so we fix the sequence
        first using the filtered global orders as boundary estimates, then
        refine each group's interior for the residency that sequence
        actually produces.  (``order_groups`` itself honors a pre-set
        ``RequestGroup.order`` for callers that re-sequence resolved
        plans.)
        """
        depth = self.program.graph.depth
        resident = (
            self.executor.residency_state() if self.warm_start
            else (None,) * depth
        )
        matrix = self._resolve_matrix()
        gm = self._planning_gate_model()
        out: List[RequestGroup] = []
        for group in groups:
            eff = effective_order(self.order, group.tasks)
            if len(eff) > 1:
                start = [
                    self.cost_model.expected_resume_load_cost(
                        resident, t, gate_model=gm
                    )
                    for t in eff
                ]
                solved = solve_suborder(
                    matrix, eff,
                    start_costs=start, constraints=self._plan_constraints,
                )
                group = dataclasses.replace(group, order=tuple(solved))
            out.append(group)
            if self.warm_start:
                resident = self.cost_model.residency_after(
                    self.group_order(group), resident
                )
            # Cold engines reset before every group: the virtual start sees
            # an empty slate each time, so ``resident`` stays all-None.
        return out

    def predicted_group_stats(
        self, groups: Sequence[RequestGroup]
    ) -> ExecutionStats:
        """Cumulative counter prediction for serving ``groups`` in sequence.

        Warm engines carry residency group-to-group (seeded from the
        executor's *current* residency), cold engines re-predict each group
        from scratch; tasks outside a group's subset count as skipped, and
        a group's re-solved per-plan order (when present) is predicted in
        place of the filtered global order.  Assumes every gate fires (gate
        outcomes are input-dependent); with no gates the executor's
        cumulative counters match this exactly.
        """
        predictor = self.cost_model.plan_predictor(
            resume=(
                self.executor.residency_state() if self.warm_start else None
            ),
            carry_residency=self.warm_start,
        )
        for g in groups:
            eff = self.group_order(g)
            predictor.append(
                eff, batch_size=g.valid,
                extra_tasks_skipped=(len(self.order) - len(eff)) * g.valid,
                collectives=(
                    self.executor.collective_view(g.xs)
                    if self.mesh is not None else None
                ),
            )
        return predictor.stats

    def expected_group_stats(
        self, groups: Sequence[RequestGroup]
    ) -> ExecutionStats:
        """Expected-counter analogue of :meth:`predicted_group_stats`:
        FLOP/task counters weighted by the cost model's gate model (fire
        and task-execution probabilities) instead of the all-gates-fire
        floor.  With no gate model this equals
        :meth:`predicted_group_stats` exactly; with a calibrated one it is
        the mean the realized counters converge to over traffic drawn from
        the calibration distribution."""
        predictor = self.cost_model.plan_predictor(
            resume=(
                self.executor.residency_state() if self.warm_start else None
            ),
            carry_residency=self.warm_start,
        )
        gm = (
            (self.cost_model.gate_model or GateModel())
            if self.adaptive is not None else None
        )
        for g in groups:
            eff = self.group_order(g)
            predictor.append(
                eff, batch_size=g.valid,
                extra_tasks_skipped=(len(self.order) - len(eff)) * g.valid,
                collectives=(
                    self.executor.collective_view(g.xs)
                    if self.mesh is not None else None
                ),
                gate_model=gm,
            )
        return predictor.expected

    # ------------------------------------------------------------ execution
    def _inject(self, site: str, **context: Any) -> None:
        """Fault-injection hook: delegates to :attr:`fault_injector` when
        armed (see ``repro.serving.reliability.FaultInjector``); a no-op
        otherwise.  Sites sit at boundaries where an injected exception is
        indistinguishable from a real one to the session's rollback/retry
        machinery."""
        if self.fault_injector is not None:
            self.fault_injector.check(site, **context)

    def _power(self, site: str, **context: Any) -> None:
        """Power-failure hook: delegates to :attr:`power_injector` when
        armed; a no-op otherwise.  Unlike :meth:`_inject`, a firing site
        raises a ``BaseException`` that kills the whole session — the
        recovery story is the durable journal, not the retry ladder."""
        if self.power_injector is not None:
            self.power_injector.check(site, **context)

    def _run_group(
        self,
        group: RequestGroup,
        eff: Sequence[int],
        executor: Optional[TaskGraphExecutor] = None,
        intermittent: Optional[IntermittentContext] = None,
        ckpt_plan: Optional[Sequence["CheckpointSite"]] = None,
        group_id: Optional[int] = None,
    ) -> Tuple[List[Dict[int, jax.Array]], ExecutionStats,
               List[TaskGateRecord]]:
        """Execute one homogeneous request group through the batched path.

        ``eff`` is the group's execution order (see :meth:`group_order`);
        ``executor`` defaults to the engine's own (the degradation ladder
        passes the off-mesh fallback executor instead).  Gates are evaluated
        per request row against that row's outputs so far.  A task runs
        (batched, once) when any row's gate fires; rows whose gate did not
        fire simply drop the task's output — exact, because a task's output
        depends only on its input row.  Flop/task counters are weighted by
        the fired-row count.  With uniform gate outcomes this equals the
        sequential per-request accounting; when outcomes diverge within a
        group, a partially-fired task's cached activations shorten the
        suffix of later tasks for *every* row, so the group can legitimately
        account fewer executed flops than the sum of solo serves — batching
        does strictly less work there.

        The third return value is the group's realized gate trace: one
        :class:`~repro.core.types.TaskGateRecord` per task of ``eff``, in
        execution order — weight-0 records for tasks every row's gate
        skipped, per-block fired-row counts when the executor carries an
        adaptive gater.  ``offered`` is always the group's valid count, so
        the trace is what :class:`~repro.adaptive.gate_model.\
GateModelCalibrator` consumes and what
        ``GraphCostModel.predicted_stats(..., gate_trace=...)`` replays to
        reproduce ``stats`` field-exactly.

        ``group_id`` (the serving session's group id) labels the executor's
        dispatch spans.
        """
        ex = executor if executor is not None else self.executor
        v = group.valid
        per_request: List[Dict[int, jax.Array]] = [dict() for _ in range(v)]
        stats = ExecutionStats()
        stats.tasks_skipped += (len(self.order) - len(eff)) * v
        trace: List[TaskGateRecord] = []
        for t in eff:
            g = self.gates.get(t)
            fire = [True] * v if g is None else [bool(g(per_request[i])) for i in range(v)]
            fired = sum(fire)
            stats.tasks_skipped += v - fired
            if fired == 0:
                trace.append(TaskGateRecord(task=t, weight=0, offered=v))
                continue
            self._inject("dispatch", task=t, group_tasks=group.tasks)
            if intermittent is not None:
                # ``stats`` rides along so a crash's PowerFailure carries
                # the partial (about-to-be-lost) counters — the benchmark's
                # re-executed-energy accounting reads them off the context.
                self._power(
                    "group", task=t, group_id=intermittent.group_id,
                    group_tasks=group.tasks, stats=stats,
                )
            row_mask = None
            if ex.gater is not None:
                # Realized-fire accounting must ignore padded rows and rows
                # whose legacy gate kept them out of this task.
                row_mask = np.zeros(int(group.xs.shape[0]), dtype=bool)
                row_mask[:v] = fire
            sites = [s for s in (ckpt_plan or ()) if s.task == t]
            if sites and intermittent is not None:
                hook = self._checkpoint_hook(
                    ex, stats, intermittent, t, sites, fired,
                )
                out = ex.run_task_batch(
                    t, group.xs, stats, weight=fired,
                    checkpoint_depths=[s.depth for s in sites],
                    checkpoint_hook=hook, row_mask=row_mask,
                    valid=v, group_id=group_id,
                )
            else:
                out = ex.run_task_batch(
                    t, group.xs, stats, weight=fired, row_mask=row_mask,
                    valid=v, group_id=group_id,
                )
            if ex.last_gate_record is not None:
                trace.append(dataclasses.replace(
                    ex.last_gate_record, offered=v
                ))
            for i in range(v):
                if fire[i]:
                    per_request[i][t] = out[i]
        return per_request, stats, trace

    def _checkpoint_hook(
        self,
        ex: TaskGraphExecutor,
        stats: ExecutionStats,
        intermittent: IntermittentContext,
        task: int,
        sites: Sequence[CheckpointSite],
        weight: int = 1,
    ) -> Callable[[int], None]:
        """Build the commit-point callback for one task's segmented suffix.

        Fired by the executor right after the block at a planned depth has
        executed: journal the freshly cached activation durably, account the
        write with the *planned* site's bytes/seconds (the same values
        :meth:`GraphCostModel.predicted_stats` adds from the same plan — the
        counter-exactness invariant extended to checkpoints), then give the
        power injector its "suffix" site — a failure here dies *after* the
        durable write, which is exactly what makes the checkpoint useful.
        """
        by_depth = {s.depth: s for s in sites}

        def hook(depth: int) -> None:
            site = by_depth[depth]
            ck = ex.activation_checkpoint(task)
            if ck is not None:
                intermittent.journal.checkpoint(
                    intermittent.group_id, site.pos, task,
                    ck.depth, ck.node, ck.value, ck.act_shape,
                )
            stats.checkpoint_bytes += site.bytes
            stats.checkpoint_seconds += site.seconds
            # ``weight`` lets a crash's consumer correct the task's upfront
            # flop accounting down to the blocks actually executed by
            # ``depth`` — the executor charges a task's whole suffix to
            # ``stats`` before dispatching it.
            self._power(
                "suffix", task=task, depth=depth,
                group_id=intermittent.group_id, stats=stats, weight=weight,
            )

        return hook

    def prefetch_group(
        self, group: RequestGroup, overlap_seconds: float = 0.0
    ) -> float:
        """Stage the next group's weight stream; returns the bytes scheduled.

        The prefetch schedule comes for free from the cost model:
        ``plan_loads`` over the group's execution order and the executor's
        *current* residency is exactly the load set ``_execute_group`` will
        account, so staging it makes the executor's ``prefetched_bytes``
        equal that group's ``weight_bytes_loaded`` by construction.  JAX
        dispatch is asynchronous, so the ``device_put`` transfers issued
        here overlap with whatever previously dispatched group is still
        executing on the device — ``overlap_seconds`` is that group's
        modelled compute window, and whatever load time exceeds it is
        staged alongside as the batch's modelled stall
        (``GraphCostModel.prefetch_stall_seconds``).

        Returns ``0.0`` without staging when the group needs no loads.
        Raising (including an injected ``"prefetch"`` fault) leaves any
        previously staged batch untouched; callers degrade to synchronous
        loading.
        """
        self._inject("prefetch", group_tasks=group.tasks, valid=group.valid)
        self._power("prefetch", group_tasks=group.tasks, valid=group.valid)
        eff = self.group_order(group)
        loads = self.cost_model.plan_loads(
            eff, self.executor.residency_state()
        )
        if not loads:
            return 0.0
        stall = self.cost_model.prefetch_stall_seconds(
            [d for d, _node in loads], overlap_seconds
        )
        self.executor.streamer.stage(loads, stall_seconds=stall)
        return float(sum(
            self.program.block_costs[d].weight_bytes for d, _node in loads
        ))

    def _execute_group(
        self,
        group: RequestGroup,
        intermittent: Optional[IntermittentContext] = None,
        first_task_resume: int = 0,
        keep_activations: bool = False,
        adaptive_threshold: Optional[float] = None,
        group_id: Optional[int] = None,
    ) -> GroupExecution:
        """Run one planned group; the session's execution primitive.

        Handles the warm/cold group boundary (keep residency and drop
        activations, or full reset), computes the group's cost prediction
        from the executor's *actual* residency right before execution (the
        incremental-prediction contract sessions rely on), executes, and
        returns everything a response needs — without building responses,
        so the session can defer future resolution behind the next group's
        planning.

        The counter prediction is computed *after* execution, conditioned
        on the realized gate trace — it still uses only the pre-execution
        residency (captured before the run), so the incremental-prediction
        contract is unchanged, and for ungated non-adaptive engines the
        trace is all-fire and the result is identical to the historical
        pre-execution prediction.  An adaptive engine additionally computes
        ``expected``, the a-priori expected-counter prediction under the
        cost model's gate model, *before* the run (it must not peek).

        ``adaptive_threshold`` overrides the gater's confidence threshold
        for this group (the session's deadline-ladder rung); thresholds are
        runtime scan inputs, so this never retraces a compiled program.

        ``intermittent`` (journal + group id) selects the power-failure-
        atomic path: the cost model places mid-suffix checkpoints
        (:meth:`GraphCostModel.plan_checkpoints`) and execution journals
        each one at the matching segment boundary.  ``first_task_resume`` /
        ``keep_activations`` serve crash recovery: a group resuming from a
        restored activation checkpoint at depth ``d`` enters with
        ``first_task_resume=d+1`` and must *not* clear the activation cache
        at the boundary — the restored checkpoint is the whole point.

        ``group_id`` is the session's id of the group, for its trace spans.
        """
        self._inject("plan", group_tasks=group.tasks, valid=group.valid)
        if keep_activations:
            # Crash recovery: residency and the restored checkpoint were
            # seeded by ``ServingSession.recover`` — touch neither.
            pass
        elif self.warm_start:
            # Warm boundary: keep residency, never the previous group's
            # activations (they belong to different inputs).
            self.executor.clear_activations()
        else:
            self.executor.reset()  # cold per group (reference semantics)
        eff = self.group_order(group)
        resume = self.executor.residency_state() if self.warm_start else None
        ckpt_plan: Optional[List[CheckpointSite]] = None
        if intermittent is not None and intermittent.checkpointing:
            ckpt_plan = self.cost_model.plan_checkpoints(
                eff, batch_size=group.valid,
                first_task_resume=first_task_resume,
            )
        if self._gater is not None and adaptive_threshold is not None:
            self._gater.threshold = float(adaptive_threshold)
        expected: Optional[ExecutionStats] = None
        if self.adaptive is not None:
            # A-priori expected counters — computed before the run so it
            # provably never peeks at realized gate outcomes.  An
            # uncalibrated engine uses the *empty* gate model (all fire
            # probabilities 1.0) rather than none at all, so the fire-row
            # counters are present and the expectation degrades to the
            # all-blocks floor instead of to the non-adaptive prediction.
            expected = self.cost_model.expected_stats(
                eff, batch_size=group.valid, resume=resume,
                collectives=self.executor.collective_view(group.xs),
                first_task_resume=first_task_resume,
                checkpoints=ckpt_plan,
                gate_model=self.cost_model.gate_model or GateModel(),
            )
            expected.tasks_skipped += (
                (len(self.order) - len(eff)) * group.valid
            )
        streamer = self.executor.streamer
        # Snapshot the stream state before the run consumes staged copies.
        staged = streamer.staged_nodes()
        pending_stall = streamer.pending_stall_seconds
        self._inject("load", group_tasks=group.tasks, resume=resume)
        per_request, stats, trace = self._run_group(
            group, eff, intermittent=intermittent, ckpt_plan=ckpt_plan,
            group_id=group_id,
        )
        with span("predict", group=group_id):
            stats.stream_stall_seconds += streamer.finish_group()
            # Realized-conditional prediction: replay the gate trace over
            # the *pre-execution* residency.  All-fire traces reproduce the
            # historical pre-execution prediction bit for bit;
            # gated/adaptive traces keep ``stats == predicted`` field-exact.
            predicted = self.cost_model.predicted_stats(
                eff, batch_size=group.valid, resume=resume,
                collectives=self.executor.collective_view(group.xs),
                first_task_resume=first_task_resume,
                checkpoints=ckpt_plan,
                gate_trace=trace,
            )
            warm_saved = 0.0
            if self.warm_start:
                # Collectives are resume-independent (they key on the
                # intra-order shared prefix), and warm_saved only reads the
                # load counter — the cold reference needs no collective
                # terms.  It DOES need ``first_task_resume``: the trace's
                # resume depths come from the executed walk, and a
                # crash-recovered group resumed mid-suffix — a cold-from-0
                # walk would reject its trace as divergent.
                cold_pred = self.cost_model.predicted_stats(
                    eff, batch_size=group.valid, gate_trace=trace,
                    first_task_resume=first_task_resume,
                )
                warm_saved = (
                    cold_pred.weight_bytes_loaded
                    - predicted.weight_bytes_loaded
                )
            if staged:
                # A prefetched group: the loads that hit staged copies
                # arrived over the stream, so predict them as prefetched
                # plus the staged batch's modelled stall.  For an ungated
                # engine the staged set *is* the load set (prefetch_group
                # planned it from the same residency), making this exact by
                # construction; a legacy gate that skipped a whole task
                # drops its staged-but-unused loads from both sides via the
                # trace.
                pf_bytes = sum(
                    self.program.block_costs[d].weight_bytes
                    for d, node in self.cost_model.plan_loads(
                        eff, resume, gate_trace=trace
                    )
                    if node in staged
                )
                if pf_bytes > 0.0:
                    predicted.prefetched_bytes = pf_bytes
                    predicted.stream_stall_seconds = pending_stall
            predicted.tasks_skipped += (
                (len(self.order) - len(eff)) * group.valid
            )
            if self._calibrator is not None:
                # Online calibration: fold this group's realized trace into
                # the gate model so expected-cost planning tracks traffic
                # drift.
                self._calibrator.observe(trace)
                self.cost_model = dataclasses.replace(
                    self.cost_model, gate_model=self._calibrator.model()
                )
                self._resolve_mat = None
        return GroupExecution(
            group=group, eff=eff, outputs=per_request, stats=stats,
            predicted=predicted, warm_saved=warm_saved,
            expected=expected, gate_trace=trace,
        )

    def execute_group_fallback(
        self,
        group: RequestGroup,
        adaptive_threshold: Optional[float] = None,
    ) -> GroupExecution:
        """Degradation-ladder rung for mesh engines: run ``group`` cold on a
        lazily built single-device executor.

        The fallback executor shares the program (and therefore produces
        identical outputs) but has no mesh, so its counters carry no
        collective bytes — and its prediction, computed cold without a
        collective view from the *same* cost model, matches those counters
        field for field (``weight_shards`` only scales derived seconds,
        never the byte counters).  It is reset before every use: degraded
        runs are the rare recovery path, and a cold run keeps the primary
        executor's rolled-back residency authoritative for every subsequent
        group's incremental prediction.
        """
        if self._fallback_executor is None:
            # Shares the engine's gater (same threshold/mode object), so a
            # degraded adaptive run gates identically to the primary path.
            self._fallback_executor = TaskGraphExecutor(
                self.program, gater=self._gater
            )
        ex = self._fallback_executor
        ex.reset()
        if self._gater is not None and adaptive_threshold is not None:
            self._gater.threshold = float(adaptive_threshold)
        eff = self.group_order(group)
        expected: Optional[ExecutionStats] = None
        if self.adaptive is not None:
            expected = self.cost_model.expected_stats(
                eff, batch_size=group.valid,
                gate_model=self.cost_model.gate_model or GateModel(),
            )
            expected.tasks_skipped += (
                (len(self.order) - len(eff)) * group.valid
            )
        per_request, stats, trace = self._run_group(group, eff, executor=ex)
        predicted = self.cost_model.predicted_stats(
            eff, batch_size=group.valid, gate_trace=trace
        )
        predicted.tasks_skipped += (len(self.order) - len(eff)) * group.valid
        return GroupExecution(
            group=group, eff=eff, outputs=per_request, stats=stats,
            predicted=predicted, warm_saved=0.0,
            expected=expected, gate_trace=trace,
        )

    def _group_responses(
        self, execution: GroupExecution
    ) -> List[MultitaskResponse]:
        """Responses for one executed group, in group-slot order."""
        stats = execution.stats
        group = execution.group
        # Per-request share of the group's cost as executed (warm stats
        # for a warm group) — not a cold-group estimate.  On a mesh each
        # chip streams only its weight slice, hence the shard divisor.
        per_req_seconds = stats.seconds(
            self.hw, weight_shards=self.weight_shards
        ) / max(group.valid, 1)
        return [
            MultitaskResponse(
                outputs=execution.outputs[slot],
                # Own copy per response: group-mates must not share a
                # mutable counter object.
                stats=dataclasses.replace(stats),
                order=self.order,
                predicted_seconds=per_req_seconds,
                group_size=group.valid,
                warm_weight_bytes_saved=execution.warm_saved,
                effective_order=execution.eff,
            )
            for slot in range(group.valid)
        ]

    # ---------------------------------------------------- one-shot wrappers
    def _serve_via_session(
        self, requests: Sequence[MultitaskRequest]
    ) -> List[MultitaskResponse]:
        """One-shot session: submit everything, drain, collect in order."""
        session = self.session()
        futures = [session.submit(r) for r in requests]
        session.drain()
        self.last_batch_stats = session.stats
        return [f.result() for f in futures]

    def serve_batch(
        self, requests: Sequence[MultitaskRequest]
    ) -> List[MultitaskResponse]:
        """Serve many requests via grouped batched execution.

        A thin wrapper over a one-shot :meth:`session`: every request is
        submitted, then the session drains under the engine's scheduling
        policy (the default :class:`GreedyBatchPolicy` admits the whole
        list as one planning batch — the exact pre-session semantics).  The
        scheduler buckets requests into homogeneous padded groups (and,
        with group ordering on, sequences them by warm boundary cost); each
        group runs the block-cached executor once with every block vmapped
        over the group, so weight loads amortise across the group's
        requests.  A warm engine keeps residency between groups — only the
        input-dependent activation caches are dropped at each boundary — so
        consecutive groups sharing a prefix skip those weight loads too.
        Responses come back in submission order.
        """
        return self._serve_via_session(requests)

    def serve(self, request: MultitaskRequest) -> MultitaskResponse:
        return self.serve_batch([request])[0]

    def serve_many(self, requests: Sequence[MultitaskRequest]) -> List[MultitaskResponse]:
        """Deprecated alias of :meth:`serve_batch` (kept for one release).

        Historically this simply aliased ``serve_batch``; it now routes
        through the same one-shot session and warns so callers migrate to
        ``serve_batch`` or an explicit :meth:`session`.
        """
        warnings.warn(
            "MultitaskEngine.serve_many is deprecated; use serve_batch() or "
            "a ServingSession (engine.session()) instead",
            DeprecationWarning,
            stacklevel=2,
        )
        return self._serve_via_session(list(requests))


# --------------------------------------------------------------------------
# LM serving
# --------------------------------------------------------------------------

class LMServer:
    """Batched prefill + greedy decode for any architecture in the zoo."""

    def __init__(self, model: ModelApi, params: Any,
                 policy: ShardingPolicy = TP_POLICY, max_len: int = 512):
        self.model = model
        self.params = params
        self.policy = policy
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, batch: model.prefill(p, batch, policy)
        )
        self._step = jax.jit(
            lambda p, tok, cache, n: model.decode_step(p, tok, cache, n, policy)
        )

    def generate(
        self, prompts: jax.Array, steps: int,
        features: Optional[jax.Array] = None,
    ) -> np.ndarray:
        """Greedy generation.  prompts: (B, S0) int32.  Returns (B, steps)."""
        cfg = self.model.cfg
        b, s0 = prompts.shape
        total = s0 + steps
        # Allocate a cache with full capacity, prefill into its prefix.
        if cfg.family == "encdec":
            batch = {"features": features, "tokens": prompts}
        else:
            batch = prompts
        logits, cache = self._prefill(self.params, batch)
        # Grow the prefill cache to full capacity (KV families only).
        cache = _grow_cache(self.model, cache, total, s0)
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cache_len = jnp.asarray(s0, jnp.int32)
        for _ in range(steps):
            out.append(np.asarray(jax.device_get(tok)))
            logits, cache = self._step(self.params, tok, cache, cache_len)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cache_len = cache_len + 1
        return np.stack(out, axis=1)


def _grow_cache(model: ModelApi, cache: Any, total: int, filled: int) -> Any:
    """Pad a prefill-sized KV cache out to ``total`` slots."""
    from repro.models.cache import EncDecCache, HybridCache, KVCache, SSMCache

    def grow_kv(kv: KVCache) -> KVCache:
        t = kv.k.shape[2]
        if t >= total:
            return kv
        pad = [(0, 0)] * kv.k.ndim
        pad[2] = (0, total - t)
        return KVCache(k=jnp.pad(kv.k, pad), v=jnp.pad(kv.v, pad))

    if isinstance(cache, KVCache):
        cfg = model.cfg
        if cfg.sliding_window is not None:
            # SWA ring never needs more than ``window`` slots; prefill's
            # linear layout (positions < window) is already ring-consistent.
            total = min(total, cfg.sliding_window)
        return grow_kv(cache)
    if isinstance(cache, SSMCache):
        return cache
    if isinstance(cache, HybridCache):
        return HybridCache(ssm=cache.ssm, kv=grow_kv(cache.kv))
    if isinstance(cache, EncDecCache):
        return EncDecCache(
            self_kv=grow_kv(cache.self_kv),
            cross_k=cache.cross_k, cross_v=cache.cross_v,
        )
    return cache
