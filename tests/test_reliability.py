"""Fault-tolerant serving: deadlines, backpressure, fault injection, and
crash-consistent group recovery.

The contract under test extends tests/test_session.py's "scheduling never
changes results" invariant through failures:

* a mid-pump failure is isolated to the failing *group* — its futures fail
  with typed ``RequestError``s (seq, task subset, tenant, group id, original
  traceback chained) while every other group serves normally, and the
  session stays fully usable afterwards;
* recovery is crash-consistent — each failed attempt rolls the executor's
  residency back to its pre-attempt snapshot, every retry re-predicts from
  the actual post-rollback residency, and only successful attempts merge
  into the counters, so ``session.stats == session.predicted`` stays exact,
  field for field, across rollbacks, retries, and degraded runs;
* under *random* fault schedules, deadlines, priorities, and admission
  orders, every submitted future reaches a terminal state (never stranded)
  and every successful response's outputs are allclose to a fault-free
  sequential serve of the same request.

Property tests run under hypothesis when installed and always under a
fixed-seed randomized fallback.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import MSP430
from repro.serving import (
    AffinityPolicy, DeadlineExceeded, EnginePolicy, FaultInjector,
    GreedyBatchPolicy, InjectedFault, MultitaskEngine, MultitaskRequest,
    QueueFull, RequestError, RequestGroupScheduler, RetryPolicy,
    SloAwarePolicy, TenantStats, WindowPolicy,
)
from tests.test_session import DIM, PROGRAM, FakeClock, _requests

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SUBSET_CHOICES = (None, (0,), (1, 2), (0, 3), (2, 1), (0, 1, 2, 3))
NO_RECOVERY = RetryPolicy(max_retries=0, degrade=False)


def _engine(**kwargs):
    kwargs.setdefault("scheduler", RequestGroupScheduler(batch_shapes=(1, 4)))
    return MultitaskEngine(PROGRAM, hw=MSP430, **kwargs)


def _reference_outputs(requests):
    """Fault-free sequential serve: the ground truth for every scenario.

    SLO metadata is stripped — the reference defines what the *outputs*
    should be, and a one-shot serve on the wall clock would spuriously
    expire any simulated-clock deadline.
    """
    eng = _engine()
    return [
        eng.serve(MultitaskRequest(x=r.x, tasks=r.tasks)) for r in requests
    ]


def _assert_allclose_response(got, ref):
    assert set(got.outputs) == set(ref.outputs)
    for t in ref.outputs:
        np.testing.assert_allclose(
            np.asarray(got.outputs[t]), np.asarray(ref.outputs[t]),
            rtol=1e-5, atol=1e-6,
        )


# --------------------------------------------------------------------------
# Unit coverage: injector, retry policy, tenant stats
# --------------------------------------------------------------------------

def test_fault_injector_script_and_determinism():
    inj = FaultInjector(script={"plan": {1}}, rates={"dispatch": 0.5}, seed=7)
    inj.check("plan")  # invocation 0: not scripted
    with pytest.raises(InjectedFault) as exc:
        inj.check("plan", group_tasks=(0, 1))
    assert exc.value.site == "plan" and exc.value.index == 1
    assert exc.value.context == {"group_tasks": (0, 1)}
    # Same seed + same call sequence => identical Bernoulli schedule.
    fires = []
    for trial in range(2):
        t = FaultInjector(rates={"dispatch": 0.5}, seed=7)
        row = []
        for i in range(50):
            try:
                t.check("dispatch")
                row.append(False)
            except InjectedFault:
                row.append(True)
        fires.append(row)
    assert fires[0] == fires[1]
    assert any(fires[0]) and not all(fires[0])


def test_fault_injector_validation_and_cap():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector(rates={"teleport": 0.1})
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector(script={"teleport": {0}})
    with pytest.raises(ValueError, match="must be in"):
        FaultInjector(rates={"plan": 1.5})
    inj = FaultInjector(rates={"plan": 1.0}, max_faults=2)
    for _ in range(2):
        with pytest.raises(InjectedFault):
            inj.check("plan")
    inj.check("plan")  # capped: no more faults
    assert inj.total_injected == 2 and inj.invocations["plan"] == 3


def test_retry_policy_backoff():
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError):
        RetryPolicy(backoff_base=-0.1)
    p = RetryPolicy(backoff_base=0.1, backoff_factor=2.0, backoff_max=0.3)
    assert p.backoff_seconds(0) == pytest.approx(0.1)
    assert p.backoff_seconds(1) == pytest.approx(0.2)
    assert p.backoff_seconds(5) == pytest.approx(0.3)  # capped
    assert RetryPolicy().backoff_seconds(3) == 0.0     # base 0 => no sleep


def test_session_backoff_uses_sleep_hook():
    slept = []
    inj = FaultInjector(script={"load": {0, 1}})
    eng = _engine(fault_injector=inj)
    s = eng.session(
        retry=RetryPolicy(max_retries=2, backoff_base=0.25, backoff_max=1.0),
        sleep=slept.append,
    )
    fut = s.submit(MultitaskRequest(
        x=jnp.asarray(np.zeros(DIM), jnp.float32)))
    s.drain()
    assert fut.error() is None
    assert slept == [pytest.approx(0.25), pytest.approx(0.5)]
    assert s.backoff_seconds == pytest.approx(0.75)


# --------------------------------------------------------------------------
# Regression: session stays usable after a mid-pump failure
# --------------------------------------------------------------------------

def test_session_usable_after_mid_pump_failure():
    """The ISSUE's named regression: poison one group mid-drain, then keep
    serving.  The queue stays consistent, subsequent submits serve
    correctly, and stats == predicted exactly over the succeeded groups."""
    rng = np.random.default_rng(21)
    subsets = [None, (0,), (1, 2), (0, 3), None, (1, 2)]
    reqs = _requests(rng, subsets)
    ref = _reference_outputs(reqs)

    # Script faults dense enough to exhaust retries AND the unfused rung
    # for whichever group dispatches first (plan fires on every attempt's
    # entry into _execute_group; the unfused rung re-enters it too).
    inj = FaultInjector(script={"plan": {0, 1, 2}})
    eng = _engine(fault_injector=inj)
    session = eng.session(retry=RetryPolicy(max_retries=1, degrade=True))
    futs = [session.submit(r) for r in reqs]
    session.drain()

    failed = [f for f in futs if f.error() is not None]
    served = [(f, r) for f, r in zip(futs, ref) if f.error() is None]
    assert failed, "the scripted faults must sink at least one group"
    assert served, "only one group may fail; the rest must serve"
    for f in failed:
        err = f.error()
        assert isinstance(err, RequestError)
        assert err.seq == f.seq and err.group_id is not None
        assert isinstance(err.__cause__, InjectedFault)
    for f, r in served:
        _assert_allclose_response(f.result(), r)
    assert session.groups_failed == 1
    assert session.stats == session.predicted

    # The session keeps serving: new submits drain to correct outputs and
    # the counter-exact invariant extends across the recovery boundary.
    eng.fault_injector = None
    futs2 = [session.submit(r) for r in reqs]
    session.drain()
    for f, r in zip(futs2, ref):
        _assert_allclose_response(f.result(), r)
    assert session.pending_count() == 0
    assert session.stats == session.predicted


def test_rollback_keeps_counters_exact_through_transient_faults():
    """Every group eventually succeeds (transient faults only): outputs
    match the fault-free run and stats == predicted stays exact even
    though several attempts were rolled back mid-group."""
    rng = np.random.default_rng(22)
    subsets = [None, (1, 2), (0, 3), None, (0,), (1, 2), (2, 1)]
    reqs = _requests(rng, subsets)
    ref = _reference_outputs(reqs)
    # One fault at each site, spread over early invocations: each fails a
    # different attempt once, then the retry goes through.
    inj = FaultInjector(script={"plan": {1}, "load": {2}, "dispatch": {3}})
    eng = _engine(fault_injector=inj)
    session = eng.session(retry=RetryPolicy(max_retries=3))
    futs = [session.submit(r) for r in reqs]
    session.drain()
    for f, r in zip(futs, ref):
        resp = f.result()
        assert resp.degraded is None
        _assert_allclose_response(resp, r)
    assert session.group_retries >= 1
    assert session.groups_failed == 0
    assert session.stats == session.predicted


def test_degraded_unfused_run_matches_and_stays_exact():
    # dispatch faults fire inside _run_group on the fused path; the
    # unfused rung re-dispatches through the same site, so cap the faults
    # to exhaust the primary attempts only.
    rng = np.random.default_rng(23)
    reqs = _requests(rng, [None, None])
    ref = _reference_outputs(reqs)
    inj = FaultInjector(rates={"dispatch": 1.0}, max_faults=2, seed=5)
    eng = _engine(fault_injector=inj)
    session = eng.session(retry=RetryPolicy(max_retries=1, degrade=True))
    futs = [session.submit(r) for r in reqs]
    session.drain()
    resp = futs[0].result()
    assert resp.degraded == "unfused" and resp.retries == 2
    for f, r in zip(futs, ref):
        _assert_allclose_response(f.result(), r)
    assert session.degraded_runs == 1
    assert session.stats == session.predicted


# --------------------------------------------------------------------------
# Mesh degradation ladder: single-device fallback rung
# --------------------------------------------------------------------------

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (forced host) devices"
)


def _mesh_engine(**kwargs):
    from jax.sharding import AxisType
    from repro.sharding.policy import TP_POLICY

    return MultitaskEngine(PROGRAM, hw=MSP430, policy=EnginePolicy(
        mesh=jax.make_mesh(
            (4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        ),
        sharding=TP_POLICY,
        scheduler=RequestGroupScheduler(batch_shapes=(1, 4)),
    ), **kwargs)


@needs_mesh
def test_mesh_fallback_rung_serves_group_on_single_device():
    """When every sharded attempt fails, the ladder's single_device rung
    serves the group cold on the off-mesh fallback executor: outputs match
    the fault-free reference, the counters stay exact (no collective bytes
    — the fallback has no mesh), and the *primary* executor keeps its
    rolled-back residency."""
    rng = np.random.default_rng(31)
    reqs = _requests(rng, [None, (1, 2)])
    ref = _reference_outputs(reqs)
    # Two primary attempts fault at dispatch; the third dispatch is the
    # fallback rung, which must go through.
    inj = FaultInjector(rates={"dispatch": 1.0}, max_faults=2, seed=9)
    eng = _mesh_engine(fault_injector=inj)
    session = eng.session(retry=RetryPolicy(max_retries=1, degrade=True))
    pre = eng.executor.residency_state()
    f0 = session.submit(reqs[0])
    session.drain()
    resp = f0.result()
    assert resp.degraded == "single_device" and resp.retries == 2
    _assert_allclose_response(resp, ref[0])
    assert session.degraded_runs == 1
    assert session.groups_failed == 0
    assert session.stats == session.predicted
    # The degraded group ran cold off-mesh: its counters carry no
    # collective traffic and the sharded executor's residency is exactly
    # the pre-attempt snapshot the rollback restored.
    assert session.stats.collective_bytes == 0
    assert eng.executor.residency_state() == pre
    # Later groups go back to the sharded primary path.
    f1 = session.submit(reqs[1])
    session.drain()
    resp1 = f1.result()
    assert resp1.degraded is None
    _assert_allclose_response(resp1, ref[1])
    assert session.stats == session.predicted
    assert session.stats.collective_bytes > 0


@needs_mesh
def test_mesh_fallback_failure_rolls_back_and_keeps_serving():
    """If the fallback rung itself fails, the residency snapshot restore
    runs, the members fail cleanly, and the session serves the next group
    normally with exact counters."""
    rng = np.random.default_rng(32)
    reqs = _requests(rng, [None, (0, 3)])
    ref = _reference_outputs(reqs)
    # 3 faults: two primary attempts + the fallback rung for group 0 only.
    inj = FaultInjector(rates={"dispatch": 1.0}, max_faults=3, seed=9)
    eng = _mesh_engine(fault_injector=inj)
    session = eng.session(retry=RetryPolicy(max_retries=1, degrade=True))
    pre = eng.executor.residency_state()
    f0 = session.submit(reqs[0])
    session.drain()
    with pytest.raises(RequestError) as exc_info:
        f0.result()
    assert isinstance(exc_info.value.__cause__, InjectedFault)
    assert session.groups_failed == 1
    assert session.degraded_runs == 0
    # Ladder exhausted without merging anything: counters untouched and
    # the sharded executor rolled back to its pre-group residency.
    assert session.stats == session.predicted
    assert eng.executor.residency_state() == pre
    # The session is still fully usable on the mesh path afterwards.
    f1 = session.submit(reqs[1])
    session.drain()
    resp = f1.result()
    assert resp.degraded is None
    _assert_allclose_response(resp, ref[1])
    assert session.stats == session.predicted
    assert session.stats.collective_bytes > 0


# --------------------------------------------------------------------------
# Deadlines, backpressure, tenants
# --------------------------------------------------------------------------

def test_deadline_expiry_before_planning():
    clock = FakeClock()
    eng = _engine()
    session = eng.session(
        policy=WindowPolicy(max_wait=10.0, max_group_size=4), clock=clock)
    rng = np.random.default_rng(24)
    x = jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)
    f_dead = session.submit(MultitaskRequest(x, deadline=1.0, tenant="a"))
    f_live = session.submit(MultitaskRequest(x, tenant="b"))
    clock.advance(2.0)  # past f_dead's deadline, below the window's max_wait
    session.step()
    assert isinstance(f_dead.error(), DeadlineExceeded)
    assert f_dead.error().tenant == "a"
    assert not f_live.done()  # still pending, not expired
    session.drain()
    assert f_live.error() is None
    assert session.requests_expired == 1
    assert session.tenant_stats("a").expired == 1
    assert session.tenant_stats("b").admitted == 1
    assert session.stats == session.predicted


def test_backpressure_reject_and_shed():
    rng = np.random.default_rng(25)
    x = jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)

    # reject: over-limit submission fails immediately, queue untouched
    s_rej = _engine().session(max_pending=2, overload="reject")
    f1, f2 = (s_rej.submit(MultitaskRequest(x)) for _ in range(2))
    f3 = s_rej.submit(MultitaskRequest(x, priority=99))
    err = f3.error()
    assert isinstance(err, QueueFull) and not err.shed
    assert s_rej.pending_count() == 2 and s_rej.requests_rejected == 1
    s_rej.drain()
    assert f1.error() is None and f2.error() is None

    # shed: a higher-priority arrival evicts the youngest lowest-priority
    # pending entry; equal priority falls back to reject
    s_shed = _engine().session(max_pending=2, overload="shed")
    f_old = s_shed.submit(MultitaskRequest(x, priority=0))
    f_young = s_shed.submit(MultitaskRequest(x, priority=0))
    f_vip = s_shed.submit(MultitaskRequest(x, priority=1))
    assert isinstance(f_young.error(), QueueFull) and f_young.error().shed
    assert not f_old.done() and not f_vip.done()
    f_equal = s_shed.submit(MultitaskRequest(x, priority=0))
    assert isinstance(f_equal.error(), QueueFull) and not f_equal.error().shed
    s_shed.drain()
    assert f_old.error() is None and f_vip.error() is None
    assert s_shed.requests_shed == 1 and s_shed.requests_rejected == 1


def test_per_tenant_quota_and_wait_aggregates():
    clock = FakeClock()
    eng = _engine()
    session = eng.session(clock=clock, max_pending_per_tenant=2)
    rng = np.random.default_rng(26)
    x = jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)
    fa = [session.submit(MultitaskRequest(x, tenant="a")) for _ in range(3)]
    fb = session.submit(MultitaskRequest(x, tenant="b"))
    # tenant a's third submit breaches its quota; tenant b is unaffected
    assert isinstance(fa[2].error(), QueueFull)
    assert fa[2].error().tenant == "a"
    assert not fb.done()
    clock.advance(1.5)
    session.drain()
    ts_a, ts_b = session.tenant_stats("a"), session.tenant_stats("b")
    assert ts_a.submitted == 3 and ts_a.admitted == 2 and ts_a.rejected == 1
    assert ts_b.submitted == 1 and ts_b.admitted == 1
    assert ts_a.mean_admission_wait == pytest.approx(1.5)
    assert ts_a.max_admission_wait == pytest.approx(1.5)
    assert session.tenant_mean_admission_wait("b") == pytest.approx(1.5)
    # global aggregates cover both tenants
    assert session.mean_admission_wait == pytest.approx(1.5)
    assert TenantStats().mean_admission_wait == 0.0


def test_slo_aware_policy_orders_by_urgency_and_affinity():
    clock = FakeClock()
    eng = _engine()
    policy = SloAwarePolicy(max_group_size=4, min_pending=99,
                            slack_threshold=0.5)
    session = eng.session(policy=policy, clock=clock)
    rng = np.random.default_rng(27)

    def req(subset, **kw):
        return MultitaskRequest(
            x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32),
            tasks=subset, **kw)

    f_lazy = session.submit(req((0,)))
    f_urgent = session.submit(req((1, 2), deadline=0.4))
    # Below min_pending and no urgency at t=0... deadline slack 0.4 <= 0.5
    # makes the (1, 2) bucket fire immediately despite thresholds.
    done = session.step()
    assert f_urgent.done() and f_urgent.error() is None
    assert not f_lazy.done()
    assert len(done) == 1
    session.drain()
    assert f_lazy.error() is None
    assert session.stats == session.predicted


def test_slo_aware_policy_starvation_override():
    clock = FakeClock()
    eng = _engine()
    policy = SloAwarePolicy(max_group_size=2, min_pending=2,
                            starvation_wait=5.0)
    session = eng.session(policy=policy, clock=clock)
    rng = np.random.default_rng(28)
    x = jnp.asarray(rng.normal(size=(DIM,)), jnp.float32)
    f_starved = session.submit(MultitaskRequest(x, tasks=(0, 3), tenant="b"))
    clock.advance(6.0)
    # Fresh affinity-friendly work arrives; the starved request has waited
    # past starvation_wait, so its bucket is admitted first regardless.
    f_fresh = session.submit(MultitaskRequest(x, tasks=(0,), tenant="a"))
    session.step()
    assert f_starved.done() and f_starved.error() is None
    assert not f_fresh.done()
    session.drain()
    assert f_fresh.error() is None


def test_session_validation():
    eng = _engine()
    with pytest.raises(ValueError, match="overload"):
        eng.session(overload="panic")
    with pytest.raises(ValueError, match="max_pending"):
        eng.session(max_pending=0)
    with pytest.raises(ValueError, match="max_pending_per_tenant"):
        eng.session(max_pending_per_tenant=0)


# --------------------------------------------------------------------------
# Property: never stranded, correct when served, exact when succeeded
# --------------------------------------------------------------------------

def _run_chaos_scenario(subset_idx, deadlines, priorities, fault_seed,
                        rates, policy_idx, max_retries):
    """One random scenario: every future terminal; successful outputs
    allclose to the fault-free sequential run; stats == predicted."""
    rng = np.random.default_rng(fault_seed)
    subsets = [SUBSET_CHOICES[i % len(SUBSET_CHOICES)] for i in subset_idx]
    reqs = []
    for i, s in enumerate(subsets):
        reqs.append(MultitaskRequest(
            x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=s,
            deadline=deadlines[i % len(deadlines)] if deadlines else None,
            priority=priorities[i % len(priorities)] if priorities else 0,
            tenant=("t0", "t1", None)[i % 3],
        ))
    ref = _reference_outputs(reqs)
    policy = (
        GreedyBatchPolicy(),
        WindowPolicy(max_wait=0.5, max_group_size=4),
        AffinityPolicy(max_group_size=4, min_pending=2),
        SloAwarePolicy(max_group_size=4, min_pending=2, slack_threshold=0.25),
    )[policy_idx % 4]
    inj = FaultInjector(rates=rates, seed=fault_seed)
    eng = _engine(fault_injector=inj)
    clock = FakeClock()
    session = eng.session(
        policy=policy, clock=clock, max_pending=6, overload="shed",
        retry=RetryPolicy(max_retries=max_retries),
    )
    futs = []
    for r in reqs:
        futs.append(session.submit(r))
        clock.advance(0.125)
        session.step()
    session.drain()

    for f, r in zip(futs, ref):
        assert f.done(), f"future {f.seq} stranded"
        if f.error() is None:
            _assert_allclose_response(f.result(), r)
        else:
            assert isinstance(f.error(), RequestError)
    assert session.pending_count() == 0
    assert session.stats == session.predicted
    # Accounting closes: every submission is admitted, rejected, or shed,
    # and every admitted request either resolved, expired... expiry happens
    # pre-admission, so: submitted = admitted + rejected + shed + expired
    # + still-pending (none after drain).
    assert session.requests_submitted == (
        session.requests_admitted + session.requests_rejected
        + session.requests_shed + session.requests_expired
    )


def test_chaos_property_fallback():
    rng = np.random.default_rng(99)
    for trial in range(6):
        n = int(rng.integers(3, 10))
        _run_chaos_scenario(
            subset_idx=list(rng.integers(0, len(SUBSET_CHOICES), n)),
            deadlines=(
                [float(d) for d in rng.uniform(0.1, 3.0, 3)]
                if trial % 2 else []
            ),
            priorities=[int(p) for p in rng.integers(0, 3, 3)],
            fault_seed=int(rng.integers(0, 2**31)),
            rates={
                "plan": float(rng.uniform(0, 0.2)),
                "load": float(rng.uniform(0, 0.2)),
                "dispatch": float(rng.uniform(0, 0.1)),
            },
            policy_idx=trial,
            max_retries=int(rng.integers(0, 3)),
        )


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(
        subset_idx=st.lists(
            st.integers(0, len(SUBSET_CHOICES) - 1), min_size=2, max_size=8),
        deadlines=st.lists(
            st.floats(0.1, 3.0, allow_nan=False), max_size=3),
        priorities=st.lists(st.integers(0, 3), max_size=3),
        fault_seed=st.integers(0, 2**31 - 1),
        plan_rate=st.floats(0.0, 0.25),
        dispatch_rate=st.floats(0.0, 0.15),
        policy_idx=st.integers(0, 3),
        max_retries=st.integers(0, 2),
    )
    def test_chaos_property(subset_idx, deadlines, priorities, fault_seed,
                            plan_rate, dispatch_rate, policy_idx,
                            max_retries):
        _run_chaos_scenario(
            subset_idx, deadlines, priorities, fault_seed,
            {"plan": plan_rate, "dispatch": dispatch_rate},
            policy_idx, max_retries,
        )
