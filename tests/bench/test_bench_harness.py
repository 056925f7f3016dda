"""The benchmark harness end to end on the CPU, at a tiny width: the result
line's shape, nothing compiled in the window, metrics found by name, the
control and the faults that ``correct`` has to catch, and the refusal to run
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, model
from bench import traffic as traffic_mod
from bench.spec import ROOT
from benchtools import on_cpu, real_limits, tiny_tree
from repro.core.executor import TaskGraphExecutor

SECONDS = 1.5
SEED = 2**33 + 12345  # above 32 bits, as the benchmark's seeds are


def run(bench, cell, trace=False, controls=()):
    with on_cpu():
        return harness.run_cell(bench, cell, SEED, SECONDS, trace, time.perf_counter(),
                                chip_check=False, controls=controls)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("checkout"), extra_metric=True)


@pytest.fixture(scope="module")
def open_traced(tiny):
    return run(tiny, "tiny-t4.open", trace=True, controls=("fp8",))


@pytest.fixture(scope="module")
def closed_run(tiny):
    return run(tiny, "tiny-t4.closed")


def test_result_line_shape(open_traced, closed_run):
    for result, _ in (open_traced, closed_run):
        assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
        assert list(result)[-1] == "checks"
        assert result["correct"] is True
        assert result["failed"] == 0 and result["attempted"] > 0
        assert set(result["checks"]) == {"logit_err", "wrong_outputs", "missing"}


def test_end_to_end_metrics_of_an_untraced_run(closed_run):
    metrics = closed_run[0]["metrics"]
    assert set(metrics) == {"latency_p50_ms", "latency_p95_ms", "requests_per_s", "setup_s"}
    assert 0 < metrics["latency_p50_ms"]["value"] <= metrics["latency_p95_ms"]["value"]
    assert metrics["requests_per_s"]["value"] > 0


def test_per_layer_metrics_and_new_metric_file(open_traced):
    result, _ = open_traced
    metrics = result["metrics"]
    # Nothing compiles in the window: set-up warmed every subset x shape.
    assert metrics["compiles_in_window"]["value"] == 0
    # The open loop reports how late it sent.
    assert metrics["generator_late_ms"]["value"] >= 0
    for name in ("admission_wait_ms", "plan_ms_per_group", "dispatches_per_request", "mfu"):
        assert metrics[name]["value"] > 0
    # A metric added as a new reader file and a BENCHMARK.json entry.
    assert metrics["requests_done"]["value"] > 0
    # The CPU has no TPU plane: the device readers find nothing and are left out.
    assert "device_idle_share" not in metrics
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_warm_up_builds_every_program_the_traffic_can_reach(tiny):
    cfg, traffic = tiny.config("tiny-t4"), tiny.traffic("tiny-open")
    subsets = traffic_mod.distinct_subsets(traffic)
    shapes = model.batch_shapes(cfg)
    plan = traffic_mod.make_plan(traffic, SEED, SECONDS, cfg["seq_len"], cfg["vocab_size"],
                                 len(cfg["num_classes"]))
    prompts = jax.device_put([p[None, :] for p in plan.prompts])
    engine = model.build_engine(model.build_program(cfg, SEED), cfg)
    tally = harness.warm_up(engine, cfg, subsets, prompts)
    # Subsets whose programs others built are served at the first shape only.
    assert 0 < tally["groups"] < len(subsets) * len(shapes)
    assert tally["programs"] > 0
    with harness.compile_counter() as built:
        for tasks in subsets:
            for shape in shapes:
                harness.serve_group(engine, tasks, shape, prompts)
    assert built["count"] == 0


def test_control_fails_the_limit_that_the_program_meets(open_traced):
    result, controls = open_traced
    limit = real_limits()["logit_err"]
    assert result["correct"] is True
    assert result["checks"]["logit_err"]["value"] <= limit
    # The fp8 reference, in the program's place, is judged by the same verdict.
    control = controls["fp8"]
    assert control["correct"] is False
    assert control["checks"]["logit_err"]["value"] > limit
    assert control["checks"]["wrong_outputs"]["value"] == 0


def _identity_block(monkeypatch):
    build = model.build_program

    def broken(cfg, seed):
        program = build(cfg, seed)
        program.block_fns[1] = lambda p, x: x  # a block that leaves its input as it was
        return program

    monkeypatch.setattr(model, "build_program", broken)


def _half_batch(monkeypatch):
    batch = TaskGraphExecutor.run_task_batch

    def broken(self, task, xs, stats, *args, **kwargs):
        out = batch(self, task, xs, stats, *args, **kwargs)
        kept = max(out.shape[0] // 2, 1)
        rest = jnp.broadcast_to(out[:kept].mean(0), (out.shape[0] - kept,) + out.shape[1:])
        return jnp.concatenate([out[:kept], rest.astype(out.dtype)])

    monkeypatch.setattr(TaskGraphExecutor, "run_task_batch", broken)


def _altered_answer(monkeypatch):
    build = model.build_program

    def broken(cfg, seed):
        program = build(cfg, seed)
        head = program.head_fns[0]
        program.head_fns[0] = lambda p, x: head(p, x).at[..., 0].add(0.5)
        return program

    monkeypatch.setattr(model, "build_program", broken)


@pytest.mark.parametrize("fault,cell", [
    (_identity_block, "tiny-t4.open"),
    (_half_batch, "tiny-t4.closed"),
    (_altered_answer, "tiny-t4.open"),
])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault, cell):
    fault(monkeypatch)
    result, _ = run(tiny, cell)
    assert result["correct"] is False
    assert result["checks"]["logit_err"]["value"] > result["checks"]["logit_err"]["limit"]


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nemo12b-t4.mixed-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_py_refuses_a_cpu_backend():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
