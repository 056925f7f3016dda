"""Integration test of the dry-run plumbing at reduced scale.

Runs in a SUBPROCESS with 8 placeholder host devices (the device count must
be set before jax initialises, which pytest's process already did), builds a
(2, 4) mesh, and lowers+compiles train/prefill/decode plans for reduced
configs through the exact code path the production dry-run uses.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax
from repro.configs import get_smoke_config
from repro.launch.specs import make_plan
from repro.launch.hlo_cost import analyze_hlo
from repro.models.config import InputShape

from jax.sharding import AxisType

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
out = {}
cases = [
    ("granite-34b", InputShape("t", 64, 8, "train")),
    ("mixtral-8x22b", InputShape("p", 64, 8, "prefill")),
    ("mamba2-780m", InputShape("d", 64, 8, "decode")),
    ("zamba2-2.7b", InputShape("d", 64, 8, "decode")),
    ("whisper-medium", InputShape("t", 64, 8, "train")),
]
with jax.set_mesh(mesh):
    for arch, shape in cases:
        cfg = get_smoke_config(arch)
        plan = make_plan(cfg, shape, mesh, "tp")
        compiled = jax.jit(
            plan.step_fn, in_shardings=plan.in_shardings,
            out_shardings=plan.out_shardings,
        ).lower(*plan.args_sds).compile()
        acc = analyze_hlo(compiled.as_text())
        out[f"{arch}/{shape.kind}"] = {
            "flops": acc["flops"], "bytes": acc["bytes"],
            "coll": acc["collective_bytes"],
        }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_make_plan_lowers_on_8_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # The child must never reach for an accelerator the parent may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, timeout=560, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 5
    for k, v in out.items():
        assert v["flops"] > 0, k
        assert v["bytes"] > 0, k
        # sharded models must communicate on a >1-device mesh
    assert sum(v["coll"] > 0 for v in out.values()) >= 3
