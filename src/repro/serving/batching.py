"""Batching schedulers for both serving paths.

* :class:`RequestGroupScheduler` — groups :class:`MultitaskRequest`s for the
  *task-graph* engine: requests are bucketed by requested task subset (and
  input shape/dtype) so every group runs one homogeneous schedule through
  ``TaskGraphExecutor.run_batch``, and each group is padded up to a small
  fixed set of batch shapes so jit recompilation stays bounded at
  ``len(batch_shapes)`` batch dims per sample shape.  Per-request gate
  outcomes are resolved by the engine while a group executes (a task's
  output depends only on the input row, so running a gated-off row and
  dropping its output is exact) — the dynamic analogue of bucketing by gate
  outcome without re-stacking mid-flight.  With a cost model supplied, the
  emitted groups are additionally *sequenced* by :func:`order_groups` so
  consecutive groups hand residency over cheaply — the paper's task-ordering
  idea lifted one level up, feeding the engine's warm-start pipeline.

* :class:`ContinuousBatcher` — continuous batching for the LM server: a
  minimal production-shaped scheduler where slots in a fixed-size batch are
  recycled the moment a sequence finishes, new prompts are prefilled into
  free slots (with right-aligned padding so cache positions line up), and
  every engine step decodes all active slots together.  This is the
  decode-shape economics the dry-run's ``serve_step`` lowers: batch =
  concurrent slots, cache_len grows per step.  For simplicity the scheduler
  keeps a single shared ``cache_len`` high-water mark per batch (slot-level
  masks handle shorter sequences) — the standard static-shape compromise
  without ragged support.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import (
    TYPE_CHECKING, Any, Deque, Dict, FrozenSet, List, Optional, Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constraints import Constraints
from repro.core.cost_model import GraphCostModel, Residency
from repro.core.ordering import greedy_2opt_order, optimal_order
from repro.core.spans import span
from repro.models.registry import ModelApi
from repro.sharding.policy import ShardingPolicy, TP_POLICY

if TYPE_CHECKING:  # avoid a module cycle with repro.serving.engine
    from repro.serving.engine import MultitaskRequest


# --------------------------------------------------------------------------
# Task-graph request grouping
# --------------------------------------------------------------------------

DEFAULT_BATCH_SHAPES = (1, 4, 16, 64)


def normalize_subset(
    tasks: Optional[Sequence[int]], num_tasks: Optional[int] = None
) -> Optional[FrozenSet[int]]:
    """A request's task subset in bucket-key form.

    ``None`` for all-tasks — implicit, or explicit when ``num_tasks`` is
    known — so full requests share a group (and its weight loads) however
    they were spelled; a frozenset otherwise.  The single normalization
    both the scheduler's bucketing and admission policies key on: they must
    agree, or a policy would score buckets that never actually form.
    """
    if tasks is None:
        return None
    subset = frozenset(int(t) for t in tasks)
    if num_tasks is not None and subset == frozenset(range(num_tasks)):
        return None
    return subset


@dataclasses.dataclass
class RequestGroup:
    """One homogeneous, padded execution group for ``run_batch``.

    Attributes:
      indices: positions of the member requests in the submitted sequence.
      requests: the member requests themselves (no padding entries).
      tasks: the shared requested task subset (``None`` = all tasks).
      xs: ``(P, *sample_shape)`` stacked inputs where ``P`` is one of the
        scheduler's padded batch shapes; rows ``valid:`` repeat the last real
        row and are dropped from outputs and logical accounting.
      valid: number of real leading rows (``len(requests)``).
      order: the group's resolved execution order, set by the engine's
        per-plan order re-solving pass (``EnginePolicy.resolve_order_per_plan``);
        ``None`` means "the engine's global order filtered to ``tasks``" —
        the default semantics every pre-session caller gets.
    """

    indices: Tuple[int, ...]
    requests: Tuple["MultitaskRequest", ...]
    tasks: Optional[FrozenSet[int]]
    xs: jnp.ndarray
    valid: int
    order: Optional[Tuple[int, ...]] = None

    @property
    def padding(self) -> int:
        return int(self.xs.shape[0]) - self.valid


class RequestGroupScheduler:
    """Bucket + chunk + pad pending multitask requests into groups.

    Invariants (property-tested):
      * every submitted request lands in exactly one group;
      * groups are homogeneous: all members share the same task subset and
        the same input shape/dtype;
      * every group's padded width is one of ``batch_shapes`` (requests
        beyond the largest shape are chunked into multiple groups);
      * padding never changes results — padded rows are replicas of the last
        real row, executed vmapped and then sliced away.

    Arrival order is preserved within a bucket so latency-sensitive callers
    get deterministic group membership.

    ``shard_multiple`` rounds every allowed batch shape up to a multiple of
    the mesh's data-shard count (``ShardingPolicy.data_shards``) so a padded
    group always splits evenly over the batch axes — the engine folds this
    in automatically when given a mesh.
    """

    def __init__(
        self,
        batch_shapes: Sequence[int] = DEFAULT_BATCH_SHAPES,
        shard_multiple: int = 1,
    ):
        m = int(shard_multiple)
        if m < 1:
            raise ValueError(f"invalid shard multiple: {shard_multiple!r}")
        shapes = tuple(sorted({-(-int(s) // m) * m for s in batch_shapes}))
        if not shapes or shapes[0] < 1:
            raise ValueError(f"invalid batch shapes: {batch_shapes!r}")
        self.batch_shapes = shapes
        self.shard_multiple = m

    def padded_size(self, n: int) -> int:
        """Smallest allowed batch shape >= ``n`` (callers chunk to the max)."""
        if n > self.batch_shapes[-1]:
            raise ValueError(
                f"group of {n} exceeds the largest batch shape "
                f"{self.batch_shapes[-1]}; chunk before padding"
            )
        for s in self.batch_shapes:
            if s >= n:
                return s
        raise AssertionError("unreachable")

    def chunk_sizes(self, n: int) -> List[Tuple[int, int]]:
        """Split a bucket of ``n`` requests into ``(take, padded_to)`` chunks.

        Greedy: peel off the largest allowed shape while it fits, and pad
        the remainder up to the next shape only when the padding does not
        exceed the remainder itself (<= 50% waste — one padded group beats
        splitting into more groups that each re-pay the weight loads).  A
        remainder below the smallest allowed shape must pad up.  E.g. with
        shapes (1, 4, 16, 64): 17 -> 16 + 1, 5 -> 4 + 1, 3 -> one chunk
        padded to 4.
        """
        out: List[Tuple[int, int]] = []
        while n > 0:
            up = next((s for s in self.batch_shapes if s >= n), None)
            down = max((s for s in self.batch_shapes if s <= n), default=None)
            if up is not None and (down is None or up - n <= n):
                out.append((n, up))
                break
            out.append((down, down))
            n -= down
        return out

    def plan(
        self,
        requests: Sequence["MultitaskRequest"],
        num_tasks: Optional[int] = None,
        cost_model: Optional[GraphCostModel] = None,
        task_order: Optional[Sequence[int]] = None,
        initial_resident: Optional[Residency] = None,
    ) -> List[RequestGroup]:
        """Partition ``requests`` into padded homogeneous groups.

        With ``num_tasks`` given, an explicit all-tasks subset is normalised
        to ``None`` so it shares a group (and its weight loads) with
        ``tasks=None`` requests.

        With ``cost_model`` and ``task_order`` given, the groups come back
        in the cost-aware inter-group sequence (:func:`order_groups`) that
        minimises the warm-start boundary loads between consecutive groups;
        otherwise bucket order is kept.  ``initial_resident`` feeds the
        engine's current residency in so a warm engine also picks the
        cheapest first group.
        """
        buckets: Dict[Tuple, List[Tuple[int, Any, jnp.ndarray]]] = {}
        for i, req in enumerate(requests):
            x = jnp.asarray(req.x)
            subset = normalize_subset(req.tasks, num_tasks)
            key = (subset, tuple(x.shape), str(x.dtype))
            buckets.setdefault(key, []).append((i, req, x))

        groups: List[RequestGroup] = []
        for (subset, _shape, _dtype), members in buckets.items():
            start = 0
            for take, p in self.chunk_sizes(len(members)):
                chunk = members[start:start + take]
                start += take
                rows = [x for (_i, _r, x) in chunk]
                rows.extend([rows[-1]] * (p - take))
                groups.append(RequestGroup(
                    indices=tuple(i for (i, _r, _x) in chunk),
                    requests=tuple(r for (_i, r, _x) in chunk),
                    tasks=subset,
                    xs=jnp.stack(rows),
                    valid=take,
                ))
        if cost_model is not None and task_order is not None:
            with span("order", groups=len(groups)):
                groups = order_groups(
                    groups, cost_model, task_order, initial_resident
                )
        return groups


# Above this many groups the exact path solvers get expensive; fall back to
# the greedy + 2-opt heuristic (the matrix is asymmetric either way).
EXACT_GROUP_ORDERING_LIMIT = 9


def effective_order(
    task_order: Sequence[int], tasks: Optional[FrozenSet[int]]
) -> List[int]:
    """The engine's task order filtered to one group's requested subset."""
    if tasks is None:
        return list(task_order)
    return [t for t in task_order if t in tasks]


def order_groups(
    groups: Sequence[RequestGroup],
    cost_model: GraphCostModel,
    task_order: Sequence[int],
    initial_resident: Optional[Residency] = None,
) -> List[RequestGroup]:
    """Cost-aware inter-group sequencing for the warm-start pipeline.

    The paper orders *tasks* so consecutive tasks share the longest prefix;
    this generalises the same idea one level up: consecutive *groups* should
    hand over residency cheaply.  The boundary cost of running group ``j``
    right after group ``i`` is the load-only switching cost from ``i``'s
    last executed task to ``j``'s first (activations never cross groups, so
    only loads are at stake), weighted by ``j``'s request count — a group of
    many requests stalling on a cold boundary costs more request-seconds
    than a singleton.  Each group's internal cost is sequence-independent,
    so minimising the boundary sum minimises the whole schedule's modelled
    cost; the matrix goes through the existing ordering machinery (exact
    Held-Karp for few groups, greedy + 2-opt beyond
    ``EXACT_GROUP_ORDERING_LIMIT``).

    ``initial_resident`` (the executor's residency before this batch) adds a
    fixed virtual start node so a warm engine also picks the cheapest *first*
    group; cold, the first group's cost is group-independent (block costs
    depend only on depth) and no virtual node is needed.
    """
    # Groups executing no tasks (empty requested subset) are residency
    # no-ops: residency flows through them untouched, so they must not sit
    # in the cost matrix as free waypoints hiding their neighbours' real
    # boundary cost.  Order the real groups, append the no-ops at the end.
    def group_eff(g: RequestGroup) -> List[int]:
        # A pre-resolved per-plan order wins over the filtered global order.
        if g.order is not None:
            return list(g.order)
        return effective_order(task_order, g.tasks)

    active = [i for i, g in enumerate(groups) if group_eff(g)]
    inert = [i for i in range(len(groups)) if i not in set(active)]
    m = len(active)
    if m <= 1:
        return [groups[i] for i in active + inert]
    firsts: List[int] = []
    lasts: List[int] = []
    for i in active:
        eff = group_eff(groups[i])
        firsts.append(eff[0])
        lasts.append(eff[-1])

    warm = initial_resident is not None and any(
        r is not None for r in initial_resident
    )
    n = m + 1 if warm else m
    off = 1 if warm else 0
    c = np.zeros((n, n), dtype=np.float64)
    for i in range(m):
        for j in range(m):
            if i == j:
                continue
            c[i + off, j + off] = (
                groups[active[j]].valid
                * cost_model.warm_switching_cost(lasts[i], firsts[j])
            )
    cons = None
    if warm:
        for j in range(m):
            c[0, j + 1] = groups[active[j]].valid * cost_model.resume_load_cost(
                initial_resident, firsts[j]
            )
        # The virtual start must come first: it precedes every group.
        cons = Constraints.make(n, precedence=[(0, j + 1) for j in range(m)])

    if n <= EXACT_GROUP_ORDERING_LIMIT:
        res = optimal_order(c, cons)
    else:
        res = greedy_2opt_order(c, cons)
    seq = [active[g - off] for g in res.order if g - off >= 0]
    return [groups[i] for i in seq + inert]


@dataclasses.dataclass
class GenRequest:
    uid: int
    prompt: np.ndarray          # (S0,) int32
    max_new_tokens: int


@dataclasses.dataclass
class GenResult:
    uid: int
    tokens: np.ndarray          # generated ids
    steps: int


class ContinuousBatcher:
    """Fixed-slot continuous batching over a ModelApi.

    The engine re-prefills the WHOLE batch whenever slot membership changes
    (simple and correct; a production engine would insert into the live
    cache).  Between membership changes, decode steps are batched.
    """

    def __init__(
        self,
        model: ModelApi,
        params: Any,
        slots: int = 4,
        max_len: int = 256,
        policy: ShardingPolicy = TP_POLICY,
        eos_token: Optional[int] = None,
    ):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.policy = policy
        self.eos = eos_token
        self.queue: Deque[GenRequest] = deque()
        self.results: List[GenResult] = []
        self._prefill = jax.jit(lambda p, b: model.prefill(p, b, policy))
        self._step = jax.jit(
            lambda p, t, c, n: model.decode_step(p, t, c, n, policy)
        )

    def submit(self, req: GenRequest) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError("request exceeds max_len")
        self.queue.append(req)

    # ------------------------------------------------------------------ run
    def run(self) -> List[GenResult]:
        """Serve until the queue drains.  Returns completed results."""
        while self.queue:
            active = [
                self.queue.popleft()
                for _ in range(min(self.slots, len(self.queue)))
            ]
            self._serve_wave(active)
        return self.results

    def _serve_wave(self, active: List[GenRequest]) -> None:
        """Prefill a wave of requests together, decode until all finish."""
        b = len(active)
        s0 = max(len(r.prompt) for r in active)
        # Right-align prompts so the last prompt token sits at position s0-1
        # for every slot; left padding repeats the first token (masked by
        # causality for generation purposes at this scale).
        toks = np.stack([
            np.pad(r.prompt, (s0 - len(r.prompt), 0), mode="edge")
            for r in active
        ]).astype(np.int32)
        logits, cache = self._prefill(self.params, jnp.asarray(toks))
        from repro.serving.engine import _grow_cache

        total = s0 + max(r.max_new_tokens for r in active)
        cache = _grow_cache(self.model, cache, total, s0)

        out: Dict[int, List[int]] = {r.uid: [] for r in active}
        done = [False] * b
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cache_len = jnp.asarray(s0, jnp.int32)
        for step in range(max(r.max_new_tokens for r in active)):
            ids = np.asarray(jax.device_get(tok))
            for i, r in enumerate(active):
                if done[i]:
                    continue
                out[r.uid].append(int(ids[i]))
                if (
                    len(out[r.uid]) >= r.max_new_tokens
                    or (self.eos is not None and ids[i] == self.eos)
                ):
                    done[i] = True
            if all(done):
                break
            logits, cache = self._step(self.params, tok, cache, cache_len)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            cache_len = cache_len + 1
        for r in active:
            self.results.append(
                GenResult(uid=r.uid, tokens=np.array(out[r.uid]), steps=len(out[r.uid]))
            )
