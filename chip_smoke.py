#!/usr/bin/env python3
"""Bring-up smoke: serve a full-width Mistral-Nemo task tree on a TPU.

Builds a four-task tree over ``mistral-nemo-12b`` at its published widths
(d_model 5120, d_ff 14336, 32 query / 8 KV heads of 128, vocab 131072) in
bf16, with the depth cut to 6 layers, the only reduction.  The tree has
three depths of 2 layers each with 1, 2 and 4 nodes, so it holds about 9 GB
of weights, made at random from ``--seed``.  Sixteen requests with mixed task
subsets go through the serving API a user calls:
``MultitaskEngine(...).session()``, ``submit``, ``drain`` and ``result``.

Every task's logits are compared with ``multitask_forward`` (no activation
cache, no fused suffix) on the same parameters, and the run fails if a
group was retried or served by a fallback rung, or if the session's
counters disagree with the cost model's prediction.  The request set is
served twice: the first pass compiles, the second is warm.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the tree sharded on a (1, 4) data x model mesh

Earlier lines of standard output are JSON records of each phase; their
seconds are a smoke's timings, not benchmark metrics.  The last line,
printed only when every check passed on a TPU, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.configs.mistral_nemo_12b import CONFIG  # noqa: E402
from repro.core.task_graph import TaskGraph  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.multitask import (  # noqa: E402
    build_transformer_program, multitask_forward, program_trainable_params,
)
from repro.serving import (  # noqa: E402
    AffinityPolicy, EnginePolicy, MultitaskEngine, MultitaskRequest,
)
from repro.sharding.policy import TP_POLICY  # noqa: E402

LAYERS = 6
SEQ_LEN = 512
GROUPS = ([[0, 1, 2, 3]], [[0, 1], [2, 3]], [[0], [1], [2], [3]])
NUM_CLASSES = (8, 8, 8, 8)
# All four tasks, both pairs and every singleton, interleaved as they arrive.
SUBSETS = (
    None, (0, 1), (2, 3), (0,),
    None, (0, 1), (2, 3), (1,),
    None, (0, 1), (2, 3), (2,),
    None, (0, 1), (2, 3), (3,),
)
# Served and reference logits both come from bf16 programs; they differ only
# in how XLA fuses and batches the same operations.
RTOL = ATOL = 5e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A check of the smoke failed; the message lists every failed check."""


def full_width_config() -> ModelConfig:
    return dataclasses.replace(CONFIG, num_layers=LAYERS)


def emit(record: Dict) -> None:
    print(json.dumps(record), flush=True)


@contextlib.contextmanager
def compile_counter():
    """Counts the XLA programs built inside the block, compiled or fetched
    from the persistent compile cache, and the seconds that took."""
    seen = {"count": 0, "seconds": 0.0}

    def listener(event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            seen["count"] += 1
            seen["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def memory_by_device() -> List[Dict]:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({
            "device": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def build_tree(cfg: ModelConfig, seq_len: int, seed: int):
    """The task tree's program and the requests, all made from ``seed``."""
    graph = TaskGraph.from_groups(GROUPS)
    prog = build_transformer_program(
        jax.random.PRNGKey(seed), graph, cfg, NUM_CLASSES, seq_len=seq_len
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (len(SUBSETS), 1, seq_len),
        0, cfg.raw_vocab_size, jnp.int32,
    )
    requests = [
        MultitaskRequest(x=tokens[i], tasks=s) for i, s in enumerate(SUBSETS)
    ]
    jax.block_until_ready((prog.node_params, prog.head_params, tokens))
    return prog, requests


def reference_logits(prog, requests) -> List[List[np.ndarray]]:
    """Every task's logits per request from the plain forward pass."""
    forward = jax.jit(lambda flat, x: multitask_forward(prog, flat, x))
    flat = program_trainable_params(prog)
    return [
        [np.asarray(o, np.float32) for o in forward(flat, r.x)]
        for r in requests
    ]


def build_engine(prog, mesh=None) -> MultitaskEngine:
    return MultitaskEngine(prog, policy=EnginePolicy(
        scheduling=AffinityPolicy(),
        mesh=mesh,
        sharding=TP_POLICY if mesh is not None else None,
    ))


def serve(engine: MultitaskEngine, requests: Sequence[MultitaskRequest]):
    """One session over all requests; returns it, the responses and the
    wall seconds until every output was ready."""
    t0 = time.perf_counter()
    session = engine.session()
    futures = [session.submit(r) for r in requests]
    session.drain()
    responses = [f.result() for f in futures]
    jax.block_until_ready([r.outputs for r in responses])
    return session, responses, time.perf_counter() - t0


def check(session, responses, requests, reference, num_tasks: int) -> Dict:
    """Raise :class:`SmokeFailure` naming every failed check; else return
    the agreement figures."""
    failures = []
    for name in ("degraded_runs", "group_retries", "plan_failures",
                 "prefetch_failures", "groups_failed", "requests_failed"):
        if getattr(session, name):
            failures.append(f"session.{name} = {getattr(session, name)}")
    if session.stats != session.predicted:
        failures.append("session.stats != session.predicted")
    max_err = 0.0
    argmax_agree = compared = 0
    for i, (req, resp) in enumerate(zip(requests, responses)):
        if resp.degraded is not None or resp.retries:
            failures.append(
                f"request {i}: degraded={resp.degraded!r} retries={resp.retries}"
            )
        want = set(range(num_tasks)) if req.tasks is None else set(req.tasks)
        if set(resp.outputs) != want:
            failures.append(f"request {i}: tasks {sorted(resp.outputs)} != {sorted(want)}")
            continue
        for t in sorted(want):
            got = np.asarray(resp.outputs[t], np.float32)
            ref = reference[i][t]
            if got.shape != ref.shape or not np.all(np.isfinite(got)):
                failures.append(f"request {i} task {t}: shape {got.shape} or non-finite")
                continue
            compared += 1
            max_err = max(max_err, float(np.max(np.abs(got - ref))))
            if not np.allclose(got, ref, rtol=RTOL, atol=ATOL):
                failures.append(f"request {i} task {t}: logits differ from the reference")
            # A flip is allowed only between two logits that are within the
            # tolerance of each other in the reference: a near tie.
            same = got.argmax(-1) == ref.argmax(-1)
            top2 = np.sort(ref, axis=-1)[..., -2:]
            tie = top2[..., 1] - top2[..., 0] <= 2 * (ATOL + RTOL * np.abs(top2[..., 1]))
            if not np.all(same | tie):
                failures.append(f"request {i} task {t}: argmax differs from the reference")
            argmax_agree += int(np.all(same))
    if failures:
        raise SmokeFailure(f"max_abs_err={max_err}: " + "; ".join(failures))
    return {
        "outputs_compared": compared,
        "argmax_agree": argmax_agree,
        "argmax_near_ties": compared - argmax_agree,
        "max_abs_err": max_err,
        "tolerance": {"rtol": RTOL, "atol": ATOL},
    }


def check_placement(engine: MultitaskEngine, n_devices: int) -> int:
    """Every placed parameter leaf of the sharded executor spans the mesh."""
    ex = engine.executor
    graph = engine.program.graph
    leaves = []
    for node in graph.nodes():
        leaves += jax.tree_util.tree_leaves(ex._node_param(node))
    for t in range(graph.num_tasks):
        leaves += jax.tree_util.tree_leaves(ex._head_param(t))
    narrow = [l.shape for l in leaves if len(l.sharding.device_set) != n_devices]
    if narrow:
        raise SmokeFailure(
            f"{len(narrow)} parameter leaves do not span {n_devices} devices: {narrow[:4]}"
        )
    return len(leaves)


def run(cfg: ModelConfig, seq_len: int, seed: int, mesh=None, log=emit) -> Dict:
    """Build, serve twice and check; ``log`` receives one record per phase."""
    t0 = time.perf_counter()
    prog, requests = build_tree(cfg, seq_len, seed)
    weight_bytes = sum(
        l.nbytes for l in jax.tree_util.tree_leaves(program_trainable_params(prog))
    )
    log({"phase": "setup", "seconds": time.perf_counter() - t0,
         "weight_bytes": weight_bytes, "nodes": len(prog.node_params),
         "devices": memory_by_device()})

    t0 = time.perf_counter()
    reference = reference_logits(prog, requests)
    log({"phase": "reference", "seconds": time.perf_counter() - t0})

    engine = build_engine(prog, mesh)
    num_tasks = prog.graph.num_tasks
    report = {}
    for name in ("first_pass", "warm_pass"):
        dispatches0 = engine.executor.dispatch_count
        with compile_counter() as compiles:
            session, responses, seconds = serve(engine, requests)
        agreement = check(session, responses, requests, reference, num_tasks)
        report[name] = {
            "phase": name,
            "smoke_timing_s": seconds,
            "compiles": compiles["count"],
            "compile_s": compiles["seconds"],
            "requests": len(responses),
            "groups": session.groups_executed,
            "admission_rounds": session.admission_rounds,
            "dispatches": engine.executor.dispatch_count - dispatches0,
            **agreement,
        }
        log(report[name])
    record = {"phase": "memory", "devices": memory_by_device()}
    if mesh is not None:
        record["leaves_spanning_mesh"] = check_placement(engine, mesh.size)
    log(record)
    report["memory"] = record
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 serves the tree sharded on a (1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    cfg = full_width_config()
    mesh = None
    if args.chips == 4:
        mesh = jax.make_mesh(
            (1, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2,
            devices=devices[:4],
        )
    emit({"phase": "config", "device_kind": kind, "device_count": len(devices),
          "chips": args.chips, "arch": CONFIG.name, "d_model": cfg.d_model,
          "d_ff": cfg.d_ff, "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
          "head_dim": cfg.head_dim, "vocab": cfg.vocab_size, "dtype": cfg.dtype,
          "seq_len": SEQ_LEN, "requests": len(SUBSETS),
          "reduced": f"num_layers {CONFIG.num_layers} -> {LAYERS}"})
    try:
        run(cfg, SEQ_LEN, args.seed, mesh=mesh)
    except Exception:  # every failure ends the smoke without a result
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": platform, "kind": kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
