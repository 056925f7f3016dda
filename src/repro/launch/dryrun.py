import os
# Append rather than overwrite: the user's own XLA_FLAGS (dump dirs, CPU
# feature flags, a test harness's device forcing) must survive.  Skip when a
# device count is already forced — jax locks it at first init anyway.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles the appropriate step function (train_step / prefill_step /
serve_step) for every requested (architecture x input-shape) combination on
the production meshes — 16x16 single-pod and 2x16x16 multi-pod — and writes
memory_analysis / cost_analysis / roofline terms to JSON.

The XLA_FLAGS line above MUST precede every other import (jax locks the
device count at first init), which is why this module sets it at line 1-2
(and why `from __future__` cannot be used here).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
      --shape train_4k --mesh single --out reports/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every combo, serial
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    RooflineReport, active_params, model_flops_estimate,
)
from repro.launch.specs import config_for_shape, make_plan, shape_supported
from repro.models.config import INPUT_SHAPES, get_shape
from repro.sharding.utils import tree_bytes


def run_one(
    arch: str,
    shape_name: str,
    mesh_kind: str = "single",
    policy: str = "auto",
    out_dir: Optional[str] = None,
    verbose: bool = True,
) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_supported(config_for_shape(cfg, shape), shape)
    if not ok:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skipped", "reason": why}
        _write(result, out_dir, arch, shape_name, mesh_kind)
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    # Monotonic clock: these are durations, and time.time() can jump under
    # NTP adjustment mid-compile.
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(mesh):
            plan = make_plan(cfg, shape, mesh, policy)
            # Decode updates its cache in place (§Perf C3): donating the
            # cache argument lets XLA alias the output buffer.
            donate = (2,) if plan.kind == "decode" else ()
            jitted = jax.jit(
                plan.step_fn,
                in_shardings=plan.in_shardings,
                out_shardings=plan.out_shardings,
                donate_argnums=donate,
            )
            lowered = jitted.lower(*plan.args_sds)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
    except Exception as e:  # lowering/compile failures are bugs: surface them
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        _write(result, out_dir, arch, shape_name, mesh_kind)
        if verbose:
            print(json.dumps({k: result[k] for k in ("arch", "shape", "mesh", "status", "error")}))
        return result

    chips = int(np.prod(list(mesh.shape.values())))
    # Trip-count-aware HLO walk (XLA's cost_analysis counts while bodies
    # once; see launch/hlo_cost.py).  The HLO module is the per-device
    # program, so flops/bytes here are PER CHIP.
    acc = analyze_hlo(hlo)
    coll = {k.replace("coll_", ""): v for k, v in acc.items() if k.startswith("coll_")}
    coll["total"] = acc["collective_bytes"]
    flops = acc["flops"] * chips          # aggregate FLOPs across chips
    bts = acc["bytes"] * chips

    n_params = int(
        tree_bytes(plan.args_sds[0])
        / np.dtype(plan.cfg.param_dtype).itemsize
    )
    n_active = active_params(plan.cfg, n_params)
    mf = model_flops_estimate(plan.cfg, shape, n_params, n_active)

    mem_d = _mem_dict(mem)
    report = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        hlo_flops=flops, hlo_bytes=bts,
        coll_bytes=coll["total"], coll_breakdown=coll,
        model_flops=mf,
        bytes_per_device=float(mem_d.get("argument_size_in_bytes", 0.0)),
        peak_memory_per_device=float(
            mem_d.get("temp_size_in_bytes", 0.0)
            + mem_d.get("argument_size_in_bytes", 0.0)
            + mem_d.get("output_size_in_bytes", 0.0)
        ),
    )
    result = {
        "status": "ok",
        "kind": plan.kind,
        "policy": plan.policy.name,
        "n_params": n_params,
        "n_active_params": n_active,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": mem_d,
        "cost_analysis": {k: float(v) for k, v in (cost or {}).items()
                          if isinstance(v, (int, float))},
        **report.to_dict(),
    }
    _write(result, out_dir, arch, shape_name, mesh_kind)
    if verbose:
        print(json.dumps({
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "ok", "policy": plan.policy.name,
            "params_B": round(n_params / 1e9, 2),
            "t_compute": f"{report.t_compute:.4f}",
            "t_memory": f"{report.t_memory:.4f}",
            "t_collective": f"{report.t_collective:.4f}",
            "dominant": report.dominant,
            "useful": f"{report.useful_flops_ratio:.3f}",
            "compile_s": result["compile_s"],
        }))
    return result


def _mem_dict(mem) -> dict:
    return {
        attr: float(getattr(mem, attr))
        for attr in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }


def _write(result: dict, out_dir: Optional[str], arch: str, shape: str, mesh: str):
    if out_dir is None:
        return
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=[s.name for s in INPUT_SHAPES])
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--policy", choices=["auto", "tp", "fsdp_tp", "expert_tp", "fsdp_expert"], default="auto")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--all", action="store_true", help="run every combo serially")
    args = ap.parse_args()

    if args.all:
        for arch in list_archs():
            for shape in INPUT_SHAPES:
                for mesh in ("single", "multi"):
                    run_one(arch, shape.name, mesh, args.policy, args.out)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required (or use --all)")
    run_one(args.arch, args.shape, args.mesh, args.policy, args.out)


if __name__ == "__main__":
    main()
