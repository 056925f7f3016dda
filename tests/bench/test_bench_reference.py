"""The plain reference keeps its readings now that a node's layers come from
the configuration's architecture file: at a tiny ``nemo12b-t4`` and a tiny
``nemo12b-t8`` shape, every task's reference logits for one seeded prompt
equal, bit for bit, values recorded from the benchmark at commit 78cb219,
before an architecture was a file of its own."""
import json
import pathlib

import numpy as np
import pytest

from bench.reference import Reference
from bench.seeds import rng
from bench.spec import Benchmark

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = json.loads((DATA / "reference_logits.json").read_text())


@pytest.mark.parametrize("name", ["tiny-t4", "tiny-t8"])
def test_reference_logits_equal_the_recorded_values(name):
    cfg = json.loads((DATA / f"{name}.json").read_text())
    seed, recorded = RECORDED["seed"], RECORDED["configs"][name]
    prompt = rng(seed, 99).integers(0, cfg["vocab_size"], cfg["seq_len"]).astype(np.int32)
    assert prompt.tolist() == recorded["prompt"]
    tasks = range(len(cfg["num_classes"]))
    logits = Reference(cfg, seed, Benchmark().arch(cfg)).logits(prompt, tasks)
    for t in tasks:
        np.testing.assert_array_equal(
            logits[t], np.asarray(recorded["logits"][str(t)], np.float32))


def test_an_unknown_model_type_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError, match=r"archs/no_such_model\.py"):
        Benchmark().arch({"model_type": "no_such_model"})
