"""Mesh-sharded request-group serving sweep: single-device vs TP vs FSDP+TP.

A cycling-subset request trace is served three ways on a forced 8-host-device
CPU topology (one ``(data=4, model=2)`` mesh):

* **single** — the unsharded engine (the PR-5 serving path);
* **tp** — ``EnginePolicy(mesh, TP_POLICY)``: batch over ``data``, fused
  suffix weights tensor-parallel over ``model`` (weights 2-way sharded);
* **fsdp_tp** — ``FSDP_TP_POLICY``: weights additionally ZeRO-sharded over
  ``data`` (8-way), traded against per-suffix all-gather traffic.

Checks run on every configuration (dry-run included):

* sharded outputs match the single-device engine (allclose);
* every session's executed counters equal its incremental cost-model
  prediction **exactly**, including the per-kind collective-byte counters
  (nonzero on both sharded engines);
* the predicted collective bytes equal an independent ``HloCostModel``
  re-measurement over the lowered suffix programs the plan dispatches;
* the gate: the best sharded policy's modelled per-request seconds
  (``ExecutionStats.seconds(hw, weight_shards)`` on an MCU-class model with
  an attached inter-chip link) improve on single-device by **>= 1.2x** —
  each chip streams only its weight slice, and the collective traffic the
  sharding buys must not eat the saving.

Everything is modelled from exact counters (no wall-clock), so the gate is
deterministic.  Machine-readable results land in the ``mesh_sweep`` section
of ``BENCH_serving.json``.

Usage: ``PYTHONPATH=src python benchmarks/serving_mesh.py [--dry-run]``
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import argparse
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

if __package__ in (None, ""):  # `python benchmarks/serving_mesh.py`
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from benchmarks.common import emit, update_bench_json
from benchmarks.serving_batch import build_program
from benchmarks.serving_groups import SUBSETS
from repro.core import MSP430
from repro.launch.hlo_cost import analyze_hlo
from repro.serving import (
    EnginePolicy, MultitaskEngine, MultitaskRequest, RequestGroupScheduler,
)
from repro.sharding.policy import FSDP_TP_POLICY, TP_POLICY

SPEEDUP_GATE = 1.2   # best sharded modelled seconds vs single-device
# The MCU cost model with an inter-chip link attached (MSP430 has none):
# weight streaming stays the bottleneck, collectives ride a 50 MB/s link.
HW = dataclasses.replace(MSP430, link_bw=50e6)

COLLECTIVE_FIELDS = ("all-gather", "all-reduce", "reduce-scatter")


def trace_requests(n_requests: int, dim: int, seed: int = 5):
    rng = np.random.default_rng(seed)
    return [
        MultitaskRequest(
            x=jnp.asarray(rng.normal(size=(dim,)), jnp.float32),
            tasks=SUBSETS[i % len(SUBSETS)],
        )
        for i in range(n_requests)
    ]


def measured_collectives(engine, groups):
    """Independent per-kind re-measurement of the plan's collective bytes:
    ``analyze_hlo`` over the exact lowered suffix program of every dispatch
    (``prev`` resets per group — activations never cross groups)."""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes (the sweep is deterministic either way)")
    ap.add_argument("--dim", type=int, default=None,
                    help="block width (default 64, dry-run 16)")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default 48, dry-run 16)")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable results file ('' disables)")
    args = ap.parse_args(argv)

    if jax.device_count() < 8:
        print(f"FAIL: needs 8 host devices, got {jax.device_count()} "
              "(XLA_FLAGS was locked before this script ran)",
              file=sys.stderr)
        return 1

    dim = args.dim or (16 if args.dry_run else 64)
    n_req = args.requests or (16 if args.dry_run else 48)
    shapes = (1, 4)  # the engine rounds these up to data-shard multiples

    prog = build_program(dim)
    reqs = trace_requests(n_req, dim)
    mesh = jax.make_mesh(
        (4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    configs = {
        "single": None,
        "tp": TP_POLICY,
        "fsdp_tp": FSDP_TP_POLICY,
    }

    print("name,us_per_call,derived")
    rows = {}
    baseline_outputs = None
    for name, sharding in configs.items():
        eng = MultitaskEngine(prog, hw=HW, policy=EnginePolicy(
            mesh=mesh if sharding is not None else None,
            sharding=sharding,
            scheduler=RequestGroupScheduler(batch_shapes=shapes),
        ))
        groups = eng.plan_groups(reqs)
        measured, measured_other = (
            measured_collectives(eng, groups) if sharding is not None
            else ({k: 0.0 for k in COLLECTIVE_FIELDS}, 0.0)
        )

        session = eng.session()
        futures = [session.submit(r) for r in reqs]
        session.drain()
        resp = [f.result() for f in futures]
        stats = session.stats

        # Counters match the incremental prediction exactly — including the
        # collective terms (no gates on these engines).
        assert stats == session.predicted, (
            f"{name}: executed counters diverge from the incremental "
            f"prediction\n  got  {stats}\n  want {session.predicted}")
        # Predicted collective bytes equal the independent HLO measurement.
        assert stats.all_gather_bytes == measured["all-gather"], name
        assert stats.all_reduce_bytes == measured["all-reduce"], name
        assert stats.reduce_scatter_bytes == measured["reduce-scatter"], name
        assert stats.other_collective_bytes == measured_other, name
        if sharding is not None:
            assert stats.collective_bytes > 0, (
                f"{name}: sharded serving must communicate")

        if baseline_outputs is None:
            baseline_outputs = resp
        else:
            for r, s in zip(resp, baseline_outputs):
                assert set(r.outputs) == set(s.outputs)
                for t in r.outputs:
                    np.testing.assert_allclose(
                        np.asarray(r.outputs[t]), np.asarray(s.outputs[t]),
                        rtol=1e-5, atol=1e-5)

        per_req = stats.seconds(HW, weight_shards=eng.weight_shards) / n_req
        emit(f"serve_mesh_{name}", per_req * 1e6,
             f"modelled_per_request;weight_shards={eng.weight_shards};"
             f"data_shards={eng.data_shards};"
             f"collective_bytes={stats.collective_bytes:.0f}")
        rows[name] = {
            "weight_shards": eng.weight_shards,
            "data_shards": eng.data_shards,
            "batch_shapes": list(eng.scheduler.batch_shapes),
            "groups": session.groups_executed,
            "weight_bytes_loaded": stats.weight_bytes_loaded,
            "all_gather_bytes": stats.all_gather_bytes,
            "all_reduce_bytes": stats.all_reduce_bytes,
            "reduce_scatter_bytes": stats.reduce_scatter_bytes,
            "other_collective_bytes": stats.other_collective_bytes,
            "modelled_per_request_seconds": per_req,
        }

    best_name, best = min(
        ((n, r) for n, r in rows.items() if n != "single"),
        key=lambda nr: nr[1]["modelled_per_request_seconds"],
    )
    speedup = (
        rows["single"]["modelled_per_request_seconds"]
        / max(best["modelled_per_request_seconds"], 1e-30)
    )
    rows["best_sharded"] = best_name
    rows["best_sharded_speedup_vs_single"] = speedup
    if args.json:
        update_bench_json(args.json, "mesh_sweep", {
            "dim": dim, "requests": n_req, "dry_run": bool(args.dry_run),
            "mesh": {"data": 4, "model": 2},
            "link_bw": HW.link_bw, "speedup_gate": SPEEDUP_GATE,
            "rows": rows,
        })
    if speedup < SPEEDUP_GATE:
        print(f"FAIL: best sharded policy ({best_name}) modelled speedup "
              f"{speedup:.2f}x < {SPEEDUP_GATE}x vs single-device",
              file=sys.stderr)
        return 1
    print(f"# best sharded policy {best_name}: {speedup:.2f}x modelled "
          f"per-request speedup vs single-device (>= {SPEEDUP_GATE}x)")
    print("# equivalence + exact-counter + HLO-measurement checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
