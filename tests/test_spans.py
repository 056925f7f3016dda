"""Program spans, program names and padded-row counters.

A tiny session served under ``jax.profiler.trace`` writes the ``repro.*``
spans into the profiler's own trace, nested as the serving path nests
(pump > admit, plan > order, group > dispatch, predict, resolve), with the
ids that tie each dispatch to its group, task, resume depth and rows.  Every
fused program is named by its resume depth and rows, and the executor counts
the rows it dispatched and how many of them were padding.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MSP430
from repro.core import spans
from repro.core.executor import TaskGraphExecutor
from repro.serving import (
    EnginePolicy, MultitaskEngine, MultitaskRequest, RequestGroupScheduler,
)
from tests.test_session import DIM, PROGRAM

SPANS = ("pump", "admit", "plan", "order", "group", "dispatch", "predict",
         "resolve")
# Each span's innermost enclosing program span.
PARENT = {"admit": "pump", "plan": "pump", "order": "plan", "group": "pump",
          "dispatch": "group", "predict": "group", "resolve": "group"}


def _requests(subsets, seed=0):
    rng = np.random.default_rng(seed)
    return [MultitaskRequest(x=jnp.asarray(rng.normal(size=(DIM,)), jnp.float32),
                             tasks=s) for s in subsets]


def _engine(resolve=False):
    return MultitaskEngine(
        PROGRAM, hw=MSP430, policy=EnginePolicy(resolve_order_per_plan=resolve),
        scheduler=RequestGroupScheduler(batch_shapes=(1, 4)))


def _recording(engine):
    """Wraps ``engine._execute_group`` to keep each served group's id, the
    group, and the (task, resume) pairs it ran."""
    served = []
    execute = engine._execute_group

    def run(group, **kwargs):
        execution = execute(group, **kwargs)
        served.append((kwargs.get("group_id"), group,
                       [(r.task, r.resume) for r in execution.gate_trace]))
        return execution

    engine._execute_group = run
    return served


def _program_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name[len(spans.PREFIX):], float(e.start_ns),
                                float(e.start_ns + e.duration_ns), dict(e.stats)))
    return out


def _parent(event, events):
    """The innermost other span that encloses ``event``."""
    _, a, b, _ = event
    around = [e for e in events if e is not event and e[1] <= a and b <= e[2]]
    return max(around, key=lambda e: (e[1], -e[2]), default=None)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    engine = _engine(resolve=True)
    served = _recording(engine)
    subsets = [None, None, None, (0,), (1, 2), (2, 1), (0, 3)]
    with jax.profiler.trace(str(trace_dir)):
        session = engine.session()
        futures = [session.submit(r) for r in _requests(subsets)]
        session.drain()
        jax.block_until_ready([f.result().outputs for f in futures])
    return _program_spans(trace_dir), served, session


def test_every_span_is_recorded(traced):
    events, _, _ = traced
    assert {e[0] for e in events} == set(SPANS)


def test_spans_nest_as_the_serving_path(traced):
    events, _, _ = traced
    for e in events:
        parent = _parent(e, events)
        if e[0] == "pump":
            assert parent is None
        else:
            assert parent is not None and parent[0] == PARENT[e[0]], (e, parent)


def test_dispatch_spans_carry_their_group_task_resume_and_rows(traced):
    events, served, session = traced
    groups = {e[3]["group"]: e for e in events if e[0] == "group"}
    assert len(groups) == len(served) == session.groups_executed
    for group_id, group, ran in served:
        span = groups[group_id]
        assert span[3]["rows"] == group.xs.shape[0]
        assert span[3]["valid"] == group.valid and span[3]["attempt"] == 1
        dispatches = sorted((e for e in events if e[0] == "dispatch"
                             and e[3]["group"] == group_id), key=lambda e: e[1])
        assert [(e[3]["task"], e[3]["resume"]) for e in dispatches] == ran
        assert all(e[3]["rows"] == group.xs.shape[0] for e in dispatches)
        assert all(_parent(e, events) is span for e in dispatches)
        (predict,) = [e for e in events if e[0] == "predict"
                      and e[3]["group"] == group_id]
        assert _parent(predict, events) is span


def test_plan_span_counts_requests_and_groups(traced):
    events, served, session = traced
    plans = [e for e in events if e[0] == "plan"]
    assert sum(e[3]["requests"] for e in plans) == session.requests_admitted
    assert sum(e[3]["groups"] for e in plans) == len(served)
    admits = [e for e in events if e[0] == "admit"]
    assert sum(e[3].get("admitted", 0) for e in admits) == session.requests_admitted


def test_no_span_is_built_without_a_trace():
    assert spans.span("dispatch", task=1) is spans.span("plan")
    with spans.span("group", group=0) as s:
        s.set_metadata(attempt=1)


def _lowered(ex, task, resume, batched, shape):
    fn, mode = ex._fused_fn(task, resume, batched, shape, jnp.float32)
    params = (ex._stacked_suffix_params(task, resume) if mode == "scan"
              else ex._suffix_params(task, resume))
    return fn.lower(params, ex._head_param(task), jnp.zeros(shape)).as_text()


@pytest.mark.parametrize("task,resume,batched,shape,name", [
    (3, 2, True, (4, DIM), "jit_suffix_r2_b4"),
    (0, 0, True, (1, DIM), "jit_suffix_r0_b1"),
    (1, 1, False, (DIM,), "jit_suffix_r1"),
])
def test_fused_programs_are_named_by_resume_and_rows(task, resume, batched, shape, name):
    ex = TaskGraphExecutor(PROGRAM)
    assert _lowered(ex, task, resume, batched, shape).startswith(f"module @{name} ")


def test_tasks_at_one_resume_and_rows_share_one_program():
    # The module name is part of the persistent cache's key: one entry
    # serves every task that runs the same HLO.
    ex = TaskGraphExecutor(PROGRAM)
    assert _lowered(ex, 0, 1, True, (4, DIM)) == _lowered(ex, 1, 1, True, (4, DIM))


def test_segment_programs_are_named_by_depths_and_rows():
    ex = TaskGraphExecutor(PROGRAM)
    fn, mode = ex._segment_fn(2, 0, 2, True, (4, DIM), jnp.float32)
    params = (ex._stacked_segment_params(2, 0, 2) if mode == "scan"
              else ex._segment_params(2, 0, 2))
    assert fn.lower(params, jnp.zeros((4, DIM))).as_text().startswith(
        "module @jit_segment_0_2_b4 ")


@pytest.mark.parametrize("subsets", [
    # One task a request: one dispatch a group, so the padded rows are the
    # groups' padding.
    [(0,), (0,), (1,), (1,), (1,), (2,), (3,), (3,), (3,), (3,), (3,)],
    # Several tasks a request: every dispatch of a group runs its padding.
    [None, None, (1, 2), (0, 3), (0, 3), (0, 3), (2,), None, (1, 2)],
])
def test_rows_padded_counts_the_padding_of_every_dispatch(subsets):
    engine = _engine()
    served = _recording(engine)
    ex = engine.executor
    session = engine.session()
    for r in _requests(subsets, seed=1):
        session.submit(r)
    session.drain()
    assert served
    assert ex.rows_dispatched == sum(g.xs.shape[0] * len(ran) for _, g, ran in served)
    assert ex.rows_padded == sum(g.padding * len(ran) for _, g, ran in served)
    if all(s is not None and len(s) == 1 for s in subsets):
        assert ex.rows_padded == sum(g.padding for _, g, _ in served) > 0
    assert session.stats == session.predicted
