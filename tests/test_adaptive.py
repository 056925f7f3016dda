"""Input-adaptive serving: confidence gating inside fused suffixes, the
expected-cost model, and their composition with the rest of the stack.

The contracts under test:

* **Exactness** — the adaptive fused scan program (masked per-row gating)
  returns outputs identical to the eager per-block reference with the same
  gater, and its realized counters equal
  ``GraphCostModel.predicted_stats(..., gate_trace=executor.last_trace)``
  field for field.  ``threshold=inf`` reproduces the ungated engine's
  outputs and flops exactly (the all-blocks floor).
* **Modes equivalence** — for shape-preserving blocks and a pure
  confidence function, ``early_exit`` and ``per_block`` gating coincide on
  scan suffixes: a skipped row's activation is unchanged, so its
  confidence is unchanged, so it keeps skipping.
* **Expected == enumeration** (the probability-model contract) — expected
  counters equal the probability-weighted average of realized-trace
  predictions over the *full exact enumeration* of per-block Bernoulli
  gate outcomes; hypothesis-driven when installed, fixed-seed fallback
  always.
* **Composition** — adaptive gating composes with warm-start residency,
  segmented checkpoint dispatch, crash-restored activations, and
  mesh-sharded execution without breaking output equality or counter
  exactness.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.adaptive import (
    ALWAYS_FIRE, AdaptivePolicy, BlockGater, GateModel, GateModelCalibrator,
)
from repro.core import BlockCost, GraphCostModel, MSP430, MultitaskProgram
from repro.core.executor import TaskGraphExecutor
from repro.core.task_graph import TaskGraph
from repro.core.types import ExecutionStats, TaskGateRecord
from repro.serving import (
    EnginePolicy, MultitaskEngine, MultitaskRequest, RequestGroupScheduler,
)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

DIM = 8
GRAPH6 = TaskGraph.from_groups([
    [[0, 1, 2, 3, 4, 5]],
    [[0, 1, 2], [3, 4, 5]],
    [[0, 1], [2], [3], [4, 5]],
    [[0], [1], [2], [3], [4], [5]],
])


def _program(graph=GRAPH6, seed=0):
    rng = np.random.default_rng(seed)
    costs = [BlockCost(weight_bytes=100.0 * (d + 1), flops=10.0 * (d + 1))
             for d in range(graph.depth)]

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
# Mixed-difficulty inputs: small-norm rows stay under the confidence
# threshold (keep firing); large-norm tanh activations exit early.
def _inputs(rng, n):
    scale = np.where(np.arange(n) % 3 == 0, 0.2, 2.0)[:, None]
    xs = rng.normal(size=(n, DIM)) * scale
    return jnp.asarray(xs, jnp.float32)


def _gater(**kw):
    kw.setdefault("threshold", 0.5)
    return BlockGater(**kw)


def _outputs_allclose(a, b):
    assert set(a) == set(b)
    for t in a:
        np.testing.assert_allclose(
            np.asarray(a[t]), np.asarray(b[t]), rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# Executor: fused == reference, counters == trace replay
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["early_exit", "per_block"])
def test_adaptive_fused_matches_per_block_reference(mode):
    rng = np.random.default_rng(0)
    xs = _inputs(rng, 6)
    order = list(range(GRAPH6.num_tasks))

    fused = TaskGraphExecutor(PROGRAM, gater=_gater(mode=mode))
    ref = TaskGraphExecutor(PROGRAM, fused=False, gater=_gater(mode=mode))

    of, sf = fused.run_batch(xs, order)
    orf, sr = ref.run_batch(xs, order)
    _outputs_allclose(of, orf)
    assert sf == sr
    assert fused.last_trace == ref.last_trace
    assert sf.block_rows_gated > 0  # the stream actually exercised gating
    assert sf.flops_gated > 0


def test_early_exit_equals_per_block_on_scan_suffixes():
    # Shape-preserving blocks + pure confidence: a skipped row's activation
    # (and therefore confidence) never changes, so per-block re-evaluation
    # decides exactly what the sticky early-exit mask decides.
    rng = np.random.default_rng(1)
    xs = _inputs(rng, 5)
    order = [0, 3, 1, 4, 2, 5]
    ee = TaskGraphExecutor(PROGRAM, gater=_gater(mode="early_exit"))
    pb = TaskGraphExecutor(PROGRAM, gater=_gater(mode="per_block"))
    oe, se = ee.run_batch(xs, order)
    ob, sb = pb.run_batch(xs, order)
    _outputs_allclose(oe, ob)
    assert se == sb
    assert ee.last_trace == pb.last_trace


def test_executor_stats_equal_trace_replay():
    rng = np.random.default_rng(2)
    xs = _inputs(rng, 4)
    order = [2, 0, 5, 3, 1, 4]
    ex = TaskGraphExecutor(PROGRAM, gater=_gater())
    _, stats = ex.run_batch(xs, order)
    cm = GraphCostModel(GRAPH6, PROGRAM.block_costs, MSP430)
    predicted = cm.predicted_stats(
        order, batch_size=4, gate_trace=ex.last_trace)
    assert stats == predicted


def test_inf_threshold_is_all_blocks_floor():
    rng = np.random.default_rng(3)
    xs = _inputs(rng, 4)
    order = list(range(GRAPH6.num_tasks))
    gated = TaskGraphExecutor(PROGRAM, gater=_gater(threshold=ALWAYS_FIRE))
    plain = TaskGraphExecutor(PROGRAM)
    og, sg = gated.run_batch(xs, order)
    op, sp = plain.run_batch(xs, order)
    _outputs_allclose(og, op)
    assert sg.flops_gated == 0
    assert sg.block_rows_gated == 0
    assert sg.flops_executed == sp.flops_executed
    assert sg.weight_bytes_loaded == sp.weight_bytes_loaded


def test_min_blocks_floor_is_respected():
    # threshold=0 exits every row as early as allowed; min_blocks keeps the
    # first blocks of every suffix firing unconditionally.
    rng = np.random.default_rng(4)
    xs = _inputs(rng, 4)
    ex = TaskGraphExecutor(PROGRAM, gater=_gater(threshold=0.0, min_blocks=2))
    _, stats = ex.run_batch(xs, [0, 1, 2, 3, 4, 5])
    for rec in ex.last_trace:
        for i, fired in enumerate(rec.fired):
            depth = rec.resume + i
            if depth < 2:
                assert fired == rec.weight
            else:
                assert fired == 0


# --------------------------------------------------------------------------
# Expected counters == exact enumeration of gate outcomes (satellite S2)
# --------------------------------------------------------------------------

TINY = TaskGraph.from_groups([[[0, 1]], [[0], [1]]])
TINY_COSTS = [BlockCost(weight_bytes=64.0, flops=16.0),
              BlockCost(weight_bytes=32.0, flops=8.0)]


def check_expected_equals_enumeration(qs, order=(0, 1)):
    """Expected counters == sum_w P(w) * realized-trace prediction, where w
    ranges over the full product of per-(task, depth) Bernoulli outcomes.

    Per-block gating, batch 1, all task probabilities 1: every task runs,
    every executed block independently fires with probability q(t, d) —
    exactly the regime where the expectation is an exact mean by linearity.
    """
    cm = GraphCostModel(TINY, TINY_COSTS, MSP430)
    gm = GateModel(fire={
        (t, d): qs[(t, d)] for t in range(2) for d in range(2)
    })
    # Executed (task, depth) slots under `order`'s activation-resume walk.
    slots = []
    prev = None
    resumes = {}
    for t in order:
        shared = 0 if prev is None else TINY.shared_prefix_depth(prev, t)
        resumes[t] = shared
        slots.extend((t, d) for d in range(shared, TINY.depth))
        prev = t
    expected = cm.expected_stats(order, batch_size=1, gate_model=gm)
    acc = {f.name: 0.0 for f in dataclasses.fields(ExecutionStats)}
    for bits in itertools.product((0, 1), repeat=len(slots)):
        p = 1.0
        fired = {t: [] for t in order}
        for (t, d), bit in zip(slots, bits):
            q = qs[(t, d)]
            p *= q if bit else (1.0 - q)
            fired[t].append(bit)
        trace = [
            TaskGateRecord(task=t, weight=1, fired=tuple(fired[t]),
                           resume=resumes[t])
            for t in order
        ]
        stats = cm.predicted_stats(order, batch_size=1, gate_trace=trace)
        for f in dataclasses.fields(ExecutionStats):
            acc[f.name] += p * getattr(stats, f.name)
    for f in dataclasses.fields(ExecutionStats):
        assert getattr(expected, f.name) == pytest.approx(
            acc[f.name], rel=1e-9, abs=1e-9), f.name


def test_expected_equals_enumeration_fixed_seeds():
    rng = np.random.default_rng(5)
    for trial in range(6):
        qs = {(t, d): float(rng.uniform(0.0, 1.0))
              for t in range(2) for d in range(2)}
        check_expected_equals_enumeration(qs, order=(0, 1) if trial % 2
                                          else (1, 0))
    # Degenerate corners stay exact too.
    check_expected_equals_enumeration(
        {(t, d): 1.0 for t in range(2) for d in range(2)})
    check_expected_equals_enumeration(
        {(t, d): 0.0 for t in range(2) for d in range(2)})


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(
        qs=st.lists(st.floats(min_value=0.0, max_value=1.0), min_size=4,
                    max_size=4),
        flip=st.booleans(),
    )
    def test_expected_equals_enumeration_hypothesis(qs, flip):
        table = {(t, d): qs[2 * t + d] for t in range(2) for d in range(2)}
        check_expected_equals_enumeration(
            table, order=(1, 0) if flip else (0, 1))


def test_calibrated_expected_matches_measured_mean():
    # Calibrate on realized traffic, re-predict the same traffic: the
    # expected flop/fire counters must land on the measured means.
    rng = np.random.default_rng(6)
    xs = _inputs(rng, 8)
    order = list(range(GRAPH6.num_tasks))
    ex = TaskGraphExecutor(PROGRAM, gater=_gater())
    _, stats = ex.run_batch(xs, order)
    cal = GateModelCalibrator()
    cal.observe(ex.last_trace)
    cm = GraphCostModel(GRAPH6, PROGRAM.block_costs, MSP430,
                        gate_model=cal.model())
    expected = cm.expected_stats(order, batch_size=8)
    assert expected.flops_executed == pytest.approx(stats.flops_executed)
    assert expected.block_rows_fired == pytest.approx(stats.block_rows_fired)
    assert expected.block_rows_gated == pytest.approx(stats.block_rows_gated)


# --------------------------------------------------------------------------
# Composition with the rest of the stack (satellite S3)
# --------------------------------------------------------------------------

def _adaptive_engine(**engine_kw):
    policy = engine_kw.pop("policy", EnginePolicy())
    policy = dataclasses.replace(
        policy, adaptive=AdaptivePolicy(threshold=0.5))
    return MultitaskEngine(PROGRAM, hw=MSP430, policy=policy, **engine_kw)


def test_adaptive_composes_with_warm_start():
    rng = np.random.default_rng(7)
    reqs = [MultitaskRequest(x=x, tasks=s)
            for x, s in zip(_inputs(rng, 6), [None, (0, 1), (4, 5),
                                              None, (2, 3), (0, 5)])]
    warm = _adaptive_engine()
    cold = _adaptive_engine(policy=EnginePolicy(warm_start=False))
    sw = warm.session()
    fw = [sw.submit(r) for r in reqs]
    sw.drain()
    sc = cold.session()
    fc = [sc.submit(r) for r in reqs]
    sc.drain()
    assert sw.stats == sw.predicted
    assert sc.stats == sc.predicted
    # Warmth changes loads, never results.
    for a, b in zip(fw, fc):
        _outputs_allclose(a.result().outputs, b.result().outputs)
    assert sw.stats.weight_bytes_loaded <= sc.stats.weight_bytes_loaded


def test_adaptive_composes_with_segmented_checkpoints():
    # Gated segmented dispatch (the intermittent path's program shape) must
    # equal the one-shot gated suffix: each segment re-derives its alive
    # mask from the carried activation, which is exact for shape-preserving
    # confidence gating.
    rng = np.random.default_rng(8)
    xs = _inputs(rng, 4)
    one = TaskGraphExecutor(PROGRAM, gater=_gater())
    seg = TaskGraphExecutor(PROGRAM, gater=_gater())
    s1, s2 = ExecutionStats(), ExecutionStats()
    hook_depths = []
    out1 = one.run_task_batch(0, xs, s1)
    out2 = seg.run_task_batch(
        0, xs, s2, checkpoint_depths=[1, 2],
        checkpoint_hook=hook_depths.append,
    )
    np.testing.assert_allclose(
        np.asarray(out1), np.asarray(out2), rtol=1e-5, atol=1e-6)
    assert hook_depths == [1, 2]
    assert one.last_gate_record == seg.last_gate_record
    assert s1 == s2


def test_adaptive_composes_with_restored_checkpoint():
    # Crash recovery: restore the deepest cached activation into a fresh
    # executor and re-run — the gated resumed suffix must reproduce the
    # uninterrupted gated run (same outputs, same realized fire counts for
    # the resumed blocks).
    rng = np.random.default_rng(9)
    x = _inputs(rng, 4)
    full = TaskGraphExecutor(PROGRAM, gater=_gater())
    out_full = full.run_task_batch(0, x, ExecutionStats())
    rec_full = full.last_gate_record

    # A segmented run's commit hook is where the journal snapshots the
    # activation; capture the same mid-suffix checkpoint here.
    seg = TaskGraphExecutor(PROGRAM, gater=_gater())
    cks = []
    seg.run_task_batch(
        0, x, ExecutionStats(), checkpoint_depths=[2],
        checkpoint_hook=lambda _d: cks.append(seg.activation_checkpoint(0)),
    )
    ck = cks[0]
    assert ck is not None and 0 < ck.depth + 1 < GRAPH6.depth

    resumed = TaskGraphExecutor(PROGRAM, gater=_gater())
    resumed.restore_activation(ck)
    stats = ExecutionStats()
    out_res = resumed.run_task_batch(0, x, stats)
    np.testing.assert_allclose(
        np.asarray(out_full), np.asarray(out_res), rtol=1e-5, atol=1e-6)
    rec = resumed.last_gate_record
    assert rec.resume == ck.depth + 1
    # The resumed suffix's fire counts equal the tail of the full run's.
    assert rec.fired == rec_full.fired[rec.resume - rec_full.resume:]
    # And the replayed prediction stays exact for the resumed shape.
    cm = GraphCostModel(GRAPH6, PROGRAM.block_costs, MSP430)
    predicted = cm.predicted_stats(
        [0], batch_size=4, gate_trace=[rec],
        first_task_resume=rec.resume)
    assert stats == predicted


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 (forced host) devices")
def test_adaptive_composes_with_mesh():
    from jax.sharding import AxisType

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    rng = np.random.default_rng(10)
    reqs = [MultitaskRequest(x=x, tasks=s)
            for x, s in zip(_inputs(rng, 4), [None, (0, 1), (2, 3, 4), None])]
    sharded = _adaptive_engine(
        policy=EnginePolicy(mesh=mesh),
        scheduler=RequestGroupScheduler(batch_shapes=(2, 4)),
    )
    single = _adaptive_engine(
        scheduler=RequestGroupScheduler(batch_shapes=(2, 4)),
    )
    ss = sharded.session()
    fs = [ss.submit(r) for r in reqs]
    ss.drain()
    s1 = single.session()
    f1 = [s1.submit(r) for r in reqs]
    s1.drain()
    assert ss.stats == ss.predicted   # collective bytes included
    assert ss.stats.all_gather_bytes + ss.stats.all_reduce_bytes > 0
    for a, b in zip(fs, f1):
        _outputs_allclose(a.result().outputs, b.result().outputs)


@pytest.mark.slow
def test_adaptive_benchmark_full_size():
    """Nightly (cron ``pytest -m slow``): the adaptive sweep at its
    non-dry-run dimensions — all its gates (counter exactness both arms,
    >= 1.3x modelled per-request speedup, >= 99% argmax agreement,
    calibrated expected flops within 5%) must hold at full size."""
    import benchmarks.serving_adaptive as bench

    assert bench.main(["--json", ""]) == 0


def test_gate_deps_enable_resolve_for_gated_engines():
    # A gated engine with explicit gate_deps re-solves per-plan orders, and
    # every solved order keeps the gate's inputs ahead of the gated task.
    def gate(outputs):
        return bool(np.asarray(outputs[0])[0] > 0) if 0 in outputs else True

    eng = MultitaskEngine(
        PROGRAM, hw=MSP430, gates={3: gate}, gate_deps={3: (0,)},
        policy=EnginePolicy(resolve_order_per_plan=True),
    )
    rng = np.random.default_rng(11)
    reqs = [MultitaskRequest(x=x, tasks=s)
            for x, s in zip(_inputs(rng, 4), [None, (0, 3), (0, 3, 4), None])]
    groups = eng.plan_groups(reqs)
    assert any(g.order is not None for g in groups)
    for g in groups:
        order = eng.group_order(g)
        if 0 in order and 3 in order:
            assert order.index(0) < order.index(3)
    sess = eng.session()
    futs = [sess.submit(r) for r in reqs]
    sess.drain()
    assert sess.stats == sess.predicted
    for f in futs:
        assert f.result().outputs
