"""Mesh-sharded request-group serving (EnginePolicy.mesh).

The contract under test, on a forced-8-device CPU mesh (see conftest.py):

* sharding is invisible to results — sharded group serving returns outputs
  allclose to the single-device engine for random task subsets;
* cost prediction stays counter-exact — ``session.stats`` equals
  ``session.predicted`` field for field, *including* the per-kind collective
  byte counters, which are nonzero on a >1-device mesh;
* the predicted collective bytes are real, not modelled: summing
  ``HloCostModel`` (``analyze_hlo``) over the lowered suffix programs the
  plan actually dispatches reproduces the session's counters exactly.

Property-tested under hypothesis when installed, always under a fixed-seed
randomized fallback, in the style of tests/test_session.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import BlockCost, MSP430, MultitaskProgram
from repro.core.task_graph import TaskGraph
from repro.launch.hlo_cost import analyze_hlo
from repro.serving import (
    EnginePolicy, MultitaskEngine, MultitaskRequest, RequestGroupScheduler,
)
from repro.sharding.policy import FSDP_TP_POLICY, TP_POLICY

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 (forced host) devices"
)

DIM = 8
GRAPH = TaskGraph.from_groups([
    [[0, 1, 2, 3]], [[0, 1], [2, 3]], [[0], [1], [2, 3]],
])
SUBSET_CHOICES = (None, (0,), (1, 2), (0, 3), (2, 1), (0, 1, 2, 3))
COLLECTIVE_FIELDS = {
    "all-gather": "all_gather_bytes",
    "all-reduce": "all_reduce_bytes",
    "reduce-scatter": "reduce_scatter_bytes",
}


def _program(graph=GRAPH, seed=0):
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


def _requests(rng, subsets):
    return [MultitaskRequest(
        x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32), tasks=s)
        for s in subsets]


def _mesh_engine(sharding):
    return MultitaskEngine(PROGRAM, hw=MSP430, policy=EnginePolicy(
        mesh=jax.make_mesh(
            (4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        ),
        sharding=sharding,
        scheduler=RequestGroupScheduler(batch_shapes=(1, 4)),
    ))


def _measured_collectives(engine, groups):
    """Independent re-measurement: per dispatched suffix program, run the
    HLO analyzer over the exact lowered text and sum per kind.  ``prev``
    resets at every group boundary — activations never cross groups, so a
    group's first task always dispatches its full path."""
    totals = {kind: 0.0 for kind in COLLECTIVE_FIELDS}
    other = 0.0
    for g in groups:
        prev = None
        for t in engine.group_order(g):
            shared = (
                engine.program.graph.shared_prefix_depth(prev, t)
                if prev is not None else 0
            )
            acc = analyze_hlo(engine.executor.suffix_hlo(t, shared, g.xs))
            seen = 0.0
            for kind in COLLECTIVE_FIELDS:
                v = acc.get(f"coll_{kind}", 0.0)
                totals[kind] += v
                seen += v
            other += acc["collective_bytes"] - seen
            prev = t
    return totals, other


def _check_roundtrip(subsets, seed):
    rng = np.random.default_rng(seed)
    reqs = _requests(rng, subsets)
    solo = MultitaskEngine(
        PROGRAM, hw=MSP430,
        scheduler=RequestGroupScheduler(batch_shapes=(1, 4)),
    )
    solo_resp = solo.serve_batch(reqs)
    for sharding in (TP_POLICY, FSDP_TP_POLICY):
        eng = _mesh_engine(sharding)
        # Padded widths must split evenly over the 4-way data axis.
        assert all(s % eng.data_shards == 0 for s in eng.scheduler.batch_shapes)
        groups = eng.plan_groups(reqs)
        measured, measured_other = _measured_collectives(eng, groups)

        session = eng.session()
        futures = [session.submit(r) for r in reqs]
        session.drain()

        # Counter-exactness extends to the collective terms.
        assert session.stats == session.predicted
        assert session.stats.collective_bytes > 0
        # Predicted == independently HLO-measured, exactly, per kind.
        assert session.stats.all_gather_bytes == measured["all-gather"]
        assert session.stats.all_reduce_bytes == measured["all-reduce"]
        assert session.stats.reduce_scatter_bytes == measured["reduce-scatter"]
        assert session.stats.other_collective_bytes == measured_other

        # Sharding never changes results.
        for f, ref in zip(futures, solo_resp):
            resp = f.result()
            assert set(resp.outputs) == set(ref.outputs)
            for t in resp.outputs:
                np.testing.assert_allclose(
                    np.asarray(resp.outputs[t]), np.asarray(ref.outputs[t]),
                    rtol=1e-5, atol=1e-5,
                )


def test_mesh_serving_fixed_case():
    _check_roundtrip(
        [None, (0,), (1, 2), (0, 3), (2, 1), None, (1, 2), None], seed=0
    )


def test_mesh_serving_randomized_fallback():
    rng = np.random.default_rng(7)
    for trial in range(2):
        n = int(rng.integers(1, 7))
        subsets = [SUBSET_CHOICES[i]
                   for i in rng.integers(0, len(SUBSET_CHOICES), n)]
        _check_roundtrip(subsets, seed=100 + trial)


if HAVE_HYPOTHESIS:
    @settings(max_examples=3, deadline=None)
    @given(
        subsets=st.lists(
            st.sampled_from(SUBSET_CHOICES), min_size=1, max_size=6
        ),
        seed=st.integers(0, 2**16),
    )
    def test_mesh_serving_property(subsets, seed):
        _check_roundtrip(subsets, seed)


def test_single_request_on_mesh():
    eng = _mesh_engine(TP_POLICY)
    solo = MultitaskEngine(PROGRAM, hw=MSP430)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(DIM,)), jnp.float32)
    a = eng.serve(MultitaskRequest(x=x))
    b = solo.serve(MultitaskRequest(x=x))
    assert set(a.outputs) == set(b.outputs)
    for t in b.outputs:
        np.testing.assert_allclose(
            np.asarray(a.outputs[t]), np.asarray(b.outputs[t]),
            rtol=1e-5, atol=1e-5,
        )
