"""Session-based serving API: async admission, pluggable scheduling
policies, futures, and residency-aware per-plan order re-solving.

The contract under test: admission timing, scheduling policy, and per-plan
re-solving change *what gets batched together, in what order, and what gets
loaded* — never *what gets computed*.  Sessioned ``submit()`` + ``drain()``
outputs are allclose to sequential ``serve()`` for random gate outcomes,
task subsets, and admission orders, and the session's cumulative executed
counters equal its incremental cost-model prediction exactly — gated
engines included, since the prediction replays each group's realized gate
trace (``session.expected`` keeps the a-priori all-gates-fire view).

Property tests run under hypothesis when installed and always under a
fixed-seed randomized fallback.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BlockCost, Constraints, GraphCostModel, MSP430, MultitaskProgram,
)
from repro.core.cost_model import PlanPredictor
from repro.core.ordering import solve_suborder
from repro.core.task_graph import TaskGraph
from repro.serving import (
    AffinityPolicy, EnginePolicy, GreedyBatchPolicy, MultitaskEngine,
    MultitaskRequest, RequestError, RequestGroupScheduler, RetryPolicy,
    ServingSession, WindowPolicy,
)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

DIM = 8
GRAPH = TaskGraph.from_groups([
    [[0, 1, 2, 3]], [[0, 1], [2, 3]], [[0], [1], [2, 3]],
])
GRAPH6 = TaskGraph.from_groups([
    [[0, 1, 2, 3, 4, 5]],
    [[0, 1, 2], [3, 4, 5]],
    [[0, 1], [2], [3], [4, 5]],
    [[0], [1], [2], [3], [4], [5]],
])
SUBSET_CHOICES = (None, (0,), (1, 2), (0, 3), (2, 1), (0, 1, 2, 3))


def _program(graph=GRAPH, seed=0, uniform_costs=False):
    rng = np.random.default_rng(seed)
    costs = [
        BlockCost(weight_bytes=10.0, flops=1.0) if uniform_costs
        else BlockCost(weight_bytes=100.0 * (d + 1), flops=10.0 * (d + 1))
        for d in range(graph.depth)
    ]

    def block(p, x):
        return jnp.tanh(x @ p)

    node_params = {
        node: jnp.asarray(rng.normal(size=(DIM, DIM)), jnp.float32)
        for node in graph.nodes()
    }
    heads = [lambda p, x: x @ p] * graph.num_tasks
    head_params = [jnp.asarray(rng.normal(size=(DIM, 3)), jnp.float32)
                   for _ in range(graph.num_tasks)]
    return MultitaskProgram(
        graph, [block] * graph.depth, node_params, heads, head_params, costs
    )


PROGRAM = _program()


class FakeClock:
    """Deterministic session clock for admission-window tests."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t

    def __call__(self) -> float:
        return self.t


def _requests(rng, subsets):
    return [MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=s)
        for s in subsets]


# --------------------------------------------------------------------------
# One-shot parity: sessions reproduce serve_batch exactly
# --------------------------------------------------------------------------

def test_greedy_session_reproduces_serve_batch_exactly():
    rng = np.random.default_rng(0)
    subsets = [None, (0,), (1, 2), (0, 3), (2, 1), None, (1, 2)]
    reqs = _requests(rng, subsets)
    ref = MultitaskEngine(PROGRAM, hw=MSP430,
                          scheduler=RequestGroupScheduler(batch_shapes=(1, 4)))
    eng = MultitaskEngine(PROGRAM, hw=MSP430,
                          scheduler=RequestGroupScheduler(batch_shapes=(1, 4)))
    ref_resp = ref.serve_batch(reqs)

    session = eng.session()  # defaults to GreedyBatchPolicy
    futures = [session.submit(r) for r in reqs]
    assert not any(f.done() for f in futures)  # nothing runs before a pump
    session.drain()
    assert all(f.done() for f in futures)
    assert session.stats == ref.last_batch_stats
    assert session.stats == session.predicted  # no gates: counters exact
    assert session.admission_rounds == 1       # greedy = one planning batch
    assert session.requests_admitted == len(reqs)
    for f, rr in zip(futures, ref_resp):
        rs = f.result()
        assert set(rs.outputs) == set(rr.outputs)
        assert rs.group_size == rr.group_size
        # No gates: the effective order is the global order filtered to the
        # group's subset, i.e. exactly the tasks that produced outputs.
        assert rs.effective_order == tuple(
            t for t in eng.order if t in rs.outputs)
        assert rs.stats == rr.stats
        for t in rs.outputs:
            np.testing.assert_allclose(
                np.asarray(rs.outputs[t]), np.asarray(rr.outputs[t]),
                rtol=1e-5, atol=1e-6)


def test_response_effective_order_reports_group_suborder():
    rng = np.random.default_rng(1)
    eng = MultitaskEngine(PROGRAM, hw=MSP430)
    resp = eng.serve(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=(1, 2)))
    # order stays the global order; effective_order is what actually ran.
    assert resp.order == eng.order
    assert resp.effective_order == tuple(
        t for t in eng.order if t in (1, 2))
    assert set(resp.effective_order) == set(resp.outputs)
    # The group's stats describe the effective order's execution: two tasks
    # ran, the other two were subset-skipped.
    assert resp.stats.tasks_run == 2
    assert resp.stats.tasks_skipped == 2


def test_future_result_drives_drain():
    rng = np.random.default_rng(2)
    eng = MultitaskEngine(PROGRAM, hw=MSP430)
    session = eng.session()
    fut = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    assert not fut.done()
    resp = fut.result()  # drains the session on demand
    assert fut.done() and set(resp.outputs) == {0, 1, 2, 3}
    assert session.pending_count() == 0


def test_serve_many_deprecated_but_equivalent():
    rng = np.random.default_rng(3)
    reqs = _requests(rng, [None, (1, 2)])
    eng = MultitaskEngine(PROGRAM, hw=MSP430)
    ref = MultitaskEngine(PROGRAM, hw=MSP430)
    ref_resp = ref.serve_batch(reqs)
    with pytest.warns(DeprecationWarning, match="serve_many is deprecated"):
        resp = eng.serve_many(reqs)
    for rm, rr in zip(resp, ref_resp):
        assert set(rm.outputs) == set(rr.outputs)
        for t in rm.outputs:
            np.testing.assert_allclose(
                np.asarray(rm.outputs[t]), np.asarray(rr.outputs[t]),
                rtol=1e-5, atol=1e-6)


def test_pump_failure_isolated_to_failing_group():
    # A mid-pump failure (here: a gate that raises during execution) is
    # isolated to the failing *group*: its futures fail with a typed
    # RequestError chaining the original exception, drain() does not
    # raise, and requests in other groups are served normally.
    def bad_gate(outputs):
        raise ValueError("gate exploded")

    rng = np.random.default_rng(14)
    eng = MultitaskEngine(PROGRAM, hw=MSP430, gates={1: bad_gate},
                          order=[0, 1, 2, 3])
    session = eng.session(retry=RetryPolicy(max_retries=0, degrade=False))
    f_ok = session.submit(MultitaskRequest(  # no task 1: gate never runs
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=(0,)))
    f_bad = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    session.drain()  # must NOT raise: the failure rides the futures
    # Every admitted future is terminal: resolved or failed, never stuck.
    assert f_ok.done() and f_bad.done()
    assert f_ok.error() is None and f_ok.result().outputs.keys() == {0}
    with pytest.raises(RequestError, match="gate exploded") as exc:
        f_bad.result()
    assert isinstance(exc.value.__cause__, ValueError)
    assert exc.value.seq == f_bad.seq
    assert exc.value.group_id is not None
    assert session.groups_failed == 1 and session.requests_failed == 1


def test_drain_raises_on_noncompliant_policy():
    class StubbornPolicy:
        """Violates the flush contract: never admits anything."""

        def admit(self, queue, engine, now, flush):
            return []

    rng = np.random.default_rng(15)
    eng = MultitaskEngine(PROGRAM, hw=MSP430)
    session = eng.session(policy=StubbornPolicy())
    session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    with pytest.raises(RuntimeError, match="drain incomplete"):
        session.drain()


# --------------------------------------------------------------------------
# EnginePolicy config object
# --------------------------------------------------------------------------

def test_engine_policy_folds_legacy_flags():
    eng = MultitaskEngine(PROGRAM, hw=MSP430, warm_start=False,
                          group_ordering=False)
    assert eng.policy == EnginePolicy(
        warm_start=False, group_ordering=False,
        scheduling=eng.policy.scheduling, scheduler=eng.policy.scheduler)
    assert not eng.warm_start and not eng.group_ordering
    assert isinstance(eng.policy.scheduling, GreedyBatchPolicy)
    # The default scheduler is folded back into the policy: engine.policy
    # alone describes the engine's full scheduling behavior.
    assert isinstance(eng.policy.scheduler, RequestGroupScheduler)
    assert eng.scheduler is eng.policy.scheduler

    sched = RequestGroupScheduler(batch_shapes=(1, 2))
    pol = EnginePolicy(warm_start=False, scheduling=WindowPolicy(max_wait=1.0))
    eng = MultitaskEngine(PROGRAM, hw=MSP430, policy=pol, scheduler=sched)
    assert not eng.warm_start and eng.group_ordering
    assert eng.scheduler is sched
    assert eng.policy.scheduler is sched
    assert isinstance(eng.policy.scheduling, WindowPolicy)
    # Legacy kwargs override the policy object field-by-field.
    eng = MultitaskEngine(PROGRAM, hw=MSP430, policy=pol, warm_start=True)
    assert eng.warm_start


def test_policy_validation():
    with pytest.raises(ValueError):
        WindowPolicy(max_group_size=0)
    with pytest.raises(ValueError):
        WindowPolicy(max_wait=-1.0)
    with pytest.raises(ValueError):
        AffinityPolicy(max_group_size=0)


# --------------------------------------------------------------------------
# WindowPolicy: admission by max-wait / max-group-size
# --------------------------------------------------------------------------

def test_window_policy_admits_by_size_and_age():
    rng = np.random.default_rng(4)
    clock = FakeClock()
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430,
        policy=EnginePolicy(
            scheduling=WindowPolicy(max_wait=1.0, max_group_size=3)),
        scheduler=RequestGroupScheduler(batch_shapes=(1, 2, 4)),
    )
    session = eng.session(clock=clock)
    f1 = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    f2 = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    # Below both thresholds: the window holds.
    assert session.step() == []
    assert not f1.done() and session.pending_count() == 2
    # Third submission fills the window: admit all three.
    f3 = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    done = session.step()
    assert len(done) == 3 and all(f.done() for f in (f1, f2, f3))
    # A lone request is admitted once it ages past max_wait.
    f4 = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    assert session.step() == [] and not f4.done()
    clock.advance(1.5)
    assert len(session.step()) == 1 and f4.done()
    # Admission latency was recorded: three admitted at once, one at 1.5.
    assert session.requests_admitted == 4
    assert session.wait_sum == pytest.approx(1.5)
    assert session.wait_max == pytest.approx(1.5)


def test_window_policy_respects_group_size_cap():
    rng = np.random.default_rng(5)
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430,
        policy=EnginePolicy(
            scheduling=WindowPolicy(max_wait=10.0, max_group_size=2)),
        scheduler=RequestGroupScheduler(batch_shapes=(1, 2)),
    )
    session = eng.session(clock=FakeClock())
    for _ in range(5):
        session.submit(MultitaskRequest(
            x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    session.drain()
    # 5 pending drain as ceil(5/2) = 3 arrival-order admission rounds.
    assert session.admission_rounds == 3
    assert session.requests_admitted == 5


# --------------------------------------------------------------------------
# AffinityPolicy: residency-aware admission
# --------------------------------------------------------------------------

def test_affinity_policy_picks_residency_nearest_bucket():
    prog = _program(GRAPH6, seed=6)
    rng = np.random.default_rng(6)
    eng = MultitaskEngine(
        prog, hw=MSP430,
        policy=EnginePolicy(
            scheduling=AffinityPolicy(max_group_size=2), group_ordering=False),
        scheduler=RequestGroupScheduler(batch_shapes=(1, 2)),
    )
    # Warm the engine on subset (0, 1): residency ends deep in that subtree.
    eng.serve(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=(0, 1)))
    session = eng.session()
    far = session.submit(MultitaskRequest(  # other subtree, arrived first
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=(3, 4)))
    near = session.submit(MultitaskRequest(  # same subtree as the residency
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=(0, 1)))
    completed = session.flush()
    assert len(completed) == 2
    # The residency-affine bucket was admitted (and thus executed) first
    # even though the far bucket arrived earlier.
    assert near.result().effective_order[0] in (0, 1)
    assert session.admission_rounds == 2
    first_round_stats = completed[0].stats
    assert set(completed[0].outputs) == {0, 1}
    # Starting affine costs strictly fewer loads than starting cold-far:
    # the shared prefix with the previous serve stays resident.
    assert first_round_stats.weight_bytes_skipped > 0


def test_affinity_policy_min_pending_zero_admits_immediately():
    # min_pending=0 means "admit as soon as anything is pending" — it must
    # not fall back to the max_group_size threshold.
    rng = np.random.default_rng(13)
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430,
        policy=EnginePolicy(scheduling=AffinityPolicy(
            max_group_size=4, min_pending=0)),
    )
    session = eng.session(clock=FakeClock())
    f = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    assert len(session.step()) == 1 and f.done()


def test_affinity_policy_waits_below_threshold():
    rng = np.random.default_rng(7)
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430,
        policy=EnginePolicy(scheduling=AffinityPolicy(
            max_group_size=4, min_pending=3, max_wait=5.0)),
    )
    clock = FakeClock()
    session = eng.session(clock=clock)
    f = session.submit(MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)))
    assert session.step() == []        # 1 < min_pending, not aged
    clock.advance(6.0)
    assert len(session.step()) == 1    # aged out
    assert f.done()


# --------------------------------------------------------------------------
# Per-plan order re-solving
# --------------------------------------------------------------------------

def test_solve_suborder_restricts_and_seeds():
    cm = GraphCostModel(GRAPH6, _program(GRAPH6).block_costs, MSP430)
    cost = cm.cost_matrix()
    # Singleton and empty subsets pass through.
    assert solve_suborder(cost, []) == []
    assert solve_suborder(cost, [3]) == [3]
    # A subset is returned as a permutation of itself.
    sub = solve_suborder(cost, [0, 3, 1, 4])
    assert sorted(sub) == [0, 1, 3, 4]
    # Warm seeding: residency deep in {3,4,5} pulls that subtree first.
    resident = tuple(GRAPH6.path(4))
    starts = [cm.resume_load_cost(resident, t) for t in (0, 3, 1, 4)]
    sub = solve_suborder(cost, [0, 3, 1, 4], start_costs=starts)
    assert sub[0] in (3, 4) and sorted(sub) == [0, 1, 3, 4]
    # In-subset precedence pairs are kept.
    cons = Constraints.make(6, precedence=[(1, 0), (5, 2)])  # (5,2) outside
    sub = solve_suborder(cost, [0, 3, 1, 4], start_costs=starts,
                         constraints=cons)
    assert sub.index(1) < sub.index(0)
    with pytest.raises(ValueError):
        solve_suborder(cost, [0, 1], start_costs=[1.0])


def test_resolve_order_per_plan_reduces_loads_not_outputs():
    prog = _program(GRAPH6, seed=8)
    rng = np.random.default_rng(8)
    # Subsets whose filtered global order starts in the wrong subtree for a
    # warm engine: re-solving should begin at the resident subtree instead.
    subsets = [(2, 3), (0, 5), (1, 4), None, (2, 5)]
    reqs = _requests(rng, subsets)

    def engine(resolve):
        return MultitaskEngine(
            prog, hw=MSP430,
            policy=EnginePolicy(resolve_order_per_plan=resolve),
            scheduler=RequestGroupScheduler(batch_shapes=(1,)),
        )

    base, resolved = engine(False), engine(True)
    for _round in range(2):  # second round runs warm from the first
        groups = resolved.plan_groups(reqs)
        pred = resolved.predicted_group_stats(groups)
        r_resp = resolved.serve_batch(reqs)
        b_resp = base.serve_batch(reqs)
        # Counters stay exactly predictable with re-solved orders.
        assert resolved.last_batch_stats == pred
        # Re-solving picks residency-aware entry points: on this stream it
        # must not load more than the filtered-global-order baseline.
        assert (resolved.last_batch_stats.weight_bytes_loaded
                <= base.last_batch_stats.weight_bytes_loaded)
        # Work conservation: the same tasks ran, whatever the order.
        assert (resolved.last_batch_stats.tasks_run
                == base.last_batch_stats.tasks_run)
        for rr, rb in zip(r_resp, b_resp):
            assert set(rr.outputs) == set(rb.outputs)
            assert sorted(rr.effective_order) == sorted(rb.effective_order)
            for t in rr.outputs:
                np.testing.assert_allclose(
                    np.asarray(rr.outputs[t]), np.asarray(rb.outputs[t]),
                    rtol=1e-5, atol=1e-6)
    # And it actually helped somewhere on this adversarial stream.
    assert (resolved.last_batch_stats.weight_bytes_loaded
            < base.last_batch_stats.weight_bytes_loaded)


def test_resolve_order_respects_precedence_constraints():
    cons = Constraints.make(4, precedence=[(3, 1)])
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430, constraints=cons,
        policy=EnginePolicy(resolve_order_per_plan=True),
        scheduler=RequestGroupScheduler(batch_shapes=(1,)),
    )
    rng = np.random.default_rng(9)
    for subset in [(1, 3), (0, 1, 3), None]:
        resp = eng.serve(MultitaskRequest(
            x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32),
            tasks=subset))
        eff = resp.effective_order
        assert eff.index(3) < eff.index(1)


def test_resolve_order_disabled_with_gates():
    def gate(outputs):
        return bool(np.asarray(outputs[0])[0] > 0)

    eng = MultitaskEngine(
        PROGRAM, hw=MSP430, gates={1: gate}, order=[0, 1, 2, 3],
        policy=EnginePolicy(resolve_order_per_plan=True),
    )
    rng = np.random.default_rng(10)
    groups = eng.plan_groups(_requests(rng, [None, (0, 1)]))
    assert all(g.order is None for g in groups)  # gate order preserved


def test_resolve_order_with_conditional_constraints_uses_expected_costs():
    # The global order was solved under conditional execution probabilities
    # (Eq. 8).  solve_suborder rebuilds precedence-only constraints (the
    # probabilities would be dropped), so the engine re-solves over the
    # *expected* cost matrix instead — the probabilities folded into a
    # GateModel — and per-plan re-solving now runs for these engines.
    cons = Constraints.make(4, conditional=[(0, 1, 0.5)])
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430, constraints=cons,
        policy=EnginePolicy(resolve_order_per_plan=True),
    )
    rng = np.random.default_rng(16)
    groups = eng.plan_groups(_requests(rng, [None, (0, 1)]))
    # Multi-task groups get re-solved per-plan orders now.
    assert any(g.order is not None for g in groups)
    # Every re-solved order still satisfies the (precedence-folded) edges.
    for g in groups:
        if g.order is not None:
            pos = {t: k for k, t in enumerate(g.order)}
            assert all(
                pos[i] < pos[j] for (i, j) in cons.precedence
                if i in pos and j in pos
            )
    # The matrix the re-solve priced: expected switching costs, i.e. edges
    # into task 1 weighted by its 0.5 execution probability.
    mat = eng._resolve_matrix()
    exact = eng.cost_model.cost_matrix()
    for i in range(4):
        if i == 1:
            continue
        assert mat[i, 1] == pytest.approx(0.5 * exact[i, 1])
        assert mat[1, i] == pytest.approx(exact[1, i])
    # Serving through the re-solving engine stays output-identical to a
    # non-resolving one and keeps the counter-exactness invariant.
    reqs = _requests(rng, [None, (0, 1), (1, 2, 3)])
    base = MultitaskEngine(PROGRAM, hw=MSP430, constraints=cons)
    s1 = eng.session()
    f1 = [s1.submit(r) for r in reqs]
    s1.drain()
    assert s1.stats == s1.predicted
    for fa, rb in zip(f1, base.serve_batch(reqs)):
        ra = fa.result()
        assert set(ra.outputs) == set(rb.outputs)
        for t in ra.outputs:
            np.testing.assert_allclose(
                np.asarray(ra.outputs[t]), np.asarray(rb.outputs[t]),
                rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Incremental plan prediction
# --------------------------------------------------------------------------

def test_plan_predictor_matches_one_shot_prediction():
    prog = _program(GRAPH6, seed=11)
    cm = GraphCostModel(GRAPH6, prog.block_costs, MSP430)
    rng = np.random.default_rng(11)
    plan = [(list(rng.permutation(6)), int(rng.integers(1, 5)))
            for _ in range(4)]
    resume = tuple(GRAPH6.path(2))
    one_shot = cm.predicted_group_stats(plan, resume=resume)
    inc = cm.plan_predictor(resume=resume)
    deltas = [inc.append(order, b) for order, b in plan]
    assert inc.stats == one_shot
    assert inc.groups == len(plan)
    # Deltas partition the total.
    merged = deltas[0]
    for d in deltas[1:]:
        merged = merged.merge(d)
    assert merged == one_shot
    # Residency tracks the last executed task's path.
    assert inc.residency == tuple(GRAPH6.path(plan[-1][0][-1]))
    # Cold mode re-predicts each group from scratch.
    cold = cm.plan_predictor(carry_residency=False)
    for order, b in plan:
        cold.append(order, b)
    per_group = None
    for order, b in plan:
        s = cm.predicted_stats(order, batch_size=b)
        per_group = s if per_group is None else per_group.merge(s)
    assert cold.stats == per_group
    with pytest.raises(ValueError):
        PlanPredictor(cm, resume=(None,))


# --------------------------------------------------------------------------
# Property: sessioned serving == sequential serve(), counters exact
# --------------------------------------------------------------------------

POLICY_MAKERS = (
    lambda: GreedyBatchPolicy(),
    lambda: WindowPolicy(max_wait=0.5, max_group_size=3),
    lambda: AffinityPolicy(max_group_size=4, min_pending=2, max_wait=2.0),
)


def check_session_matches_sequential(spec, data_seed, policy_idx,
                                     gated, resolve):
    """Core property: any admission order/policy/gating, same outputs.

    ``spec`` is a list of (subset_index, inter-arrival-time) pairs.  The
    session serves the stream under the chosen policy with per-arrival
    ``step()`` pumps; a fresh solo engine serves each request sequentially.
    """
    rng = np.random.default_rng(data_seed)
    subsets = [SUBSET_CHOICES[i] for i, _dt in spec]
    reqs = _requests(rng, subsets)

    gates = {}
    if gated:
        # Random-but-deterministic gate outcomes keyed on the input row via
        # task 0's output (so solo and sessioned serving agree per request);
        # subsets that skip task 0 leave the gate open.
        def gate(outputs):
            if 0 not in outputs:
                return True
            return bool(np.asarray(outputs[0])[0] > 0)

        gates = {t: gate for t in range(1, 4)}
    order = [0, 1, 2, 3] if gated else None
    policy = EnginePolicy(
        scheduling=POLICY_MAKERS[policy_idx](),
        resolve_order_per_plan=resolve,
    )
    eng = MultitaskEngine(
        PROGRAM, hw=MSP430, gates=gates, order=order, policy=policy,
        scheduler=RequestGroupScheduler(batch_shapes=(1, 2, 4)),
    )
    solo = MultitaskEngine(
        PROGRAM, hw=MSP430, gates=gates, order=order,
        warm_start=False, group_ordering=False,
        scheduler=RequestGroupScheduler(batch_shapes=(1,)),
    )

    clock = FakeClock()
    session = eng.session(clock=clock)
    futures = []
    for req, (_si, dt) in zip(reqs, spec):
        clock.advance(dt)
        futures.append(session.submit(req))
        session.step()  # policy decides; may or may not admit
    session.drain()

    assert all(f.done() for f in futures)
    assert session.requests_admitted == len(reqs)
    # Cumulative executed counters == incremental prediction, exactly —
    # gated runs included: the prediction replays each group's realized
    # gate trace (legacy gate= skips carry weight-0 records).
    assert session.stats == session.predicted
    # A non-adaptive engine's a-priori expectation is the prediction's
    # all-gates-fire floor: equal when nothing gated, an upper bound else.
    assert session.expected.flops_executed >= session.stats.flops_executed
    for f, req in zip(futures, reqs):
        rs = f.result()
        ss = solo.serve(req)
        assert set(rs.outputs) == set(ss.outputs)
        assert set(rs.effective_order) >= set(rs.outputs)
        for t in rs.outputs:
            np.testing.assert_allclose(
                np.asarray(rs.outputs[t]), np.asarray(ss.outputs[t]),
                rtol=1e-5, atol=1e-6)


def test_session_matches_sequential_fixed_seeds():
    rng = np.random.default_rng(12)
    for trial in range(8):
        n = int(rng.integers(1, 9))
        spec = [(int(rng.integers(0, len(SUBSET_CHOICES))),
                 float(rng.uniform(0.0, 1.0))) for _ in range(n)]
        check_session_matches_sequential(
            spec,
            data_seed=trial,
            policy_idx=trial % len(POLICY_MAKERS),
            gated=bool(trial % 2),
            resolve=bool((trial // 2) % 2),
        )


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(
        spec=st.lists(
            st.tuples(st.integers(0, len(SUBSET_CHOICES) - 1),
                      st.floats(0.0, 2.0, allow_nan=False)),
            min_size=1, max_size=8,
        ),
        data_seed=st.integers(0, 2**16),
        policy_idx=st.integers(0, len(POLICY_MAKERS) - 1),
        gated=st.booleans(),
        resolve=st.booleans(),
    )
    def test_session_matches_sequential_hypothesis(
            spec, data_seed, policy_idx, gated, resolve):
        check_session_matches_sequential(
            spec, data_seed, policy_idx, gated, resolve)
