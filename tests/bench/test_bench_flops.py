"""The benchmark's required-FLOPs function and its table of peaks."""
import itertools
import json
import pathlib

import pytest

from bench import flops
from bench.spec import Benchmark

# Required FLOPs of every subset of both Nemo trees, recorded from the
# benchmark at commit 78cb219, before an architecture was a file of its own.
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "request_flops.json"


@pytest.fixture(scope="module")
def t4():
    return Benchmark().config("nemo12b-t4")


@pytest.fixture(scope="module")
def t8():
    return Benchmark().config("nemo12b-t8")


@pytest.fixture(scope="module")
def mistral(t4):
    return Benchmark().arch(t4)


def test_layer_flops_at_mistral_nemo_widths(t4, mistral):
    # 2 * 512 tokens * (wq + wk + wv + wo + gate/up/down) + causal attention.
    matmul_params = 5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120 + 3 * 5120 * 14336
    assert mistral.layer_flops(t4) == 2 * 512 * matmul_params + 2 * 512 * 512 * 32 * 128


def test_all_task_t4_request_counts_14_node_layers(t4, mistral):
    assert flops.request_flops(mistral, t4, range(4)) == 14 * mistral.layer_flops(t4)


def test_all_task_t8_request_counts_15_node_layers(t8, mistral):
    assert flops.request_flops(mistral, t8, range(8)) == 15 * mistral.layer_flops(t8)


@pytest.mark.parametrize("name", ["nemo12b-t4", "nemo12b-t8"])
def test_subset_flops_are_the_union_of_its_paths(name):
    bench = Benchmark()
    cfg = bench.config(name)
    arch = bench.arch(cfg)
    per_layer = arch.layer_flops(cfg)
    tasks = range(len(cfg["num_classes"]))
    for k in (1, 2, 3):
        for subset in itertools.combinations(tasks, k):
            union = set()
            for t in subset:
                union |= set(flops.path(cfg["tree"], t))
            layers = sum(cfg["layers_per_depth"][d] for d, _ in union)
            assert flops.request_flops(arch, cfg, subset) == layers * per_layer


@pytest.mark.parametrize("name", ["nemo12b-t4", "nemo12b-t8"])
def test_request_flops_equal_the_recorded_values(name):
    bench = Benchmark()
    cfg = bench.config(name)
    arch = bench.arch(cfg)
    recorded = json.loads(RECORDED.read_text())[name]
    n = len(cfg["num_classes"])
    subsets = [s for k in range(1, n + 1) for s in itertools.combinations(range(n), k)]
    assert len(recorded) == len(subsets) == 2 ** n - 1
    for subset in subsets:
        assert flops.request_flops(arch, cfg, subset) == recorded[",".join(map(str, subset))]


def test_a_node_counts_its_own_layers(t4, mistral):
    # A node's FLOPs come from its depth: a deeper node of more layers costs more.
    cfg = dict(t4, layers_per_depth=[1, 2, 3], num_hidden_layers=6)
    assert [mistral.node_flops(cfg, d) for d in range(3)] == [
        k * mistral.layer_flops(cfg) for k in (1, 2, 3)]
    assert flops.request_flops(mistral, cfg, (0,)) == 6 * mistral.layer_flops(cfg)


def test_a_shared_prefix_counts_once(t4, mistral):
    # Tasks 0 and 1 share depths 0 and 1; only the leaves differ.
    pair = flops.request_flops(mistral, t4, (0, 1))
    single = flops.request_flops(mistral, t4, (0,))
    assert pair == single + 2 * mistral.layer_flops(t4)


def test_peak_of_a_known_kind():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="table of peaks"):
        flops.peak("TPU v9 imaginary")
