"""``chip_smoke.py`` on CPU: its serving path at smoke width, its failure
checks, and that it never reports success without a TPU."""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from repro.configs.mistral_nemo_12b import SMOKE
from repro.serving import FaultInjector

ROOT = pathlib.Path(__file__).resolve().parent.parent
SEQ_LEN = 64


def _smoke_config():
    # The chip runs bf16; rehearse the same dtype at smoke width.
    return dataclasses.replace(
        SMOKE, num_layers=chip_smoke.LAYERS,
        dtype="bfloat16", param_dtype="bfloat16",
    )


def test_smoke_serving_matches_reference():
    records = []
    report = chip_smoke.run(_smoke_config(), SEQ_LEN, seed=0, log=records.append)
    phases = [r.get("phase") for r in records]
    assert phases == ["setup", "reference", "first_pass", "warm_pass", "memory"]
    # 4 all-task + 4 + 4 pair + 4 singleton requests = 36 task outputs.
    for name in ("first_pass", "warm_pass"):
        p = report[name]
        assert p["requests"] == len(chip_smoke.SUBSETS)
        assert p["outputs_compared"] == 36
        assert p["argmax_agree"] == 36
        assert p["groups"] == 7 and p["dispatches"] == 12
    assert report["first_pass"]["compiles"] > 0
    assert report["warm_pass"]["compiles"] == 0


def test_smoke_check_trips_on_unfused_rung():
    prog, requests = chip_smoke.build_tree(_smoke_config(), SEQ_LEN, seed=0)
    reference = chip_smoke.reference_logits(prog, requests)
    engine = chip_smoke.build_engine(prog)
    # The first group's primary attempt and both retries fail at dispatch,
    # so it is served by the per-block "unfused" rung.
    engine.fault_injector = FaultInjector(script={"dispatch": {0, 1, 2}})
    session, responses, _ = chip_smoke.serve(engine, requests)
    assert session.degraded_runs == 1
    assert "unfused" in {r.degraded for r in responses}
    with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
        chip_smoke.check(
            session, responses, requests, reference, prog.graph.num_tasks
        )


@pytest.mark.parametrize("alone", [False, True])
def test_main_fails_without_a_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:  # a directory holding the script and nothing else of the repo
        script = pathlib.Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env=env, cwd=script.parent, timeout=300,
    )
    assert res.returncode != 0
    for line in res.stdout.splitlines():
        if line.startswith("{"):
            assert json.loads(line).get("ok") is not True
