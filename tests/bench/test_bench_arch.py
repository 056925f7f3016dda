"""A new architecture is added as files alone.

A temporary checkout gets an architecture module ``archs/toy_gelu.py`` (a
decoder layer with a plain GELU feed-forward in place of SwiGLU, which the
program serves as ``activation="gelu"``), a tiny configuration whose
``model_type`` names it, and a cell.  The harness finds the toy's program
configuration, reference and FLOP count by ``model_type`` alone, serves the
cell on the CPU and judges it correct against the toy's own reference; no
file of the benchmark's own directory changes.
"""
import hashlib
import json
import time

import pytest

from bench import flops, harness
from bench.spec import BENCH_DIR, Benchmark
from benchtools import DATA, on_cpu, real_limits, tiny_tree

SEED = 2**33 + 2718
SECONDS = 1.5
CELL = "tiny-toy.closed"

TOY = '''"""Toy architecture: Mistral's attention, a plain GELU feed-forward."""
import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import dense, product, rmsnorm, rope
from repro.models.config import make_config

CALLS = {"model_config": 0, "init_node": 0, "apply_node": 0, "node_flops": 0}


def model_config(cfg):
    CALLS["model_config"] += 1
    return make_config(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"], activation="gelu",
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"])


def _layer_weights(key, cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    dt = jnp.dtype(cfg["torch_dtype"])
    ka, km = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    k1, k2 = jax.random.split(km)
    return {"attn_norm": jnp.ones((d,), dt), "mlp_norm": jnp.ones((d,), dt),
            "wq": dense(kq, d, (h, hd), dt), "wk": dense(kk, d, (hk, hd), dt),
            "wv": dense(kv, d, (hk, hd), dt), "wo": dense(ko, h * hd, (d,), dt),
            "w_up": dense(k1, d, (f,), dt), "w_down": dense(k2, f, (d,), dt)}


def init_node(key, cfg, depth):
    CALLS["init_node"] += 1
    n = cfg["layers_per_depth"][depth]
    return jax.vmap(lambda k: _layer_weights(k, cfg))(jax.random.split(key, n))


def _layer(x, w, cfg, mode):
    h_, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    h = rmsnorm(x, w["attn_norm"].astype(jnp.float32), eps)
    q = rope(product("sd,dhk->shk", h, w["wq"], mode), theta)
    k = jnp.repeat(rope(product("sd,dhk->shk", h, w["wk"], mode), theta), h_ // hk, axis=1)
    v = jnp.repeat(product("sd,dhk->shk", h, w["wv"], mode), h_ // hk, axis=1)
    scores = product("shk,thk->hst", q, k, mode) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = product("hst,thk->shk", att, v, mode)
    x = x + product("shk,hkd->sd", o, w["wo"].reshape(h_, hd, -1), mode)
    h = rmsnorm(x, w["mlp_norm"].astype(jnp.float32), eps)
    a = jax.nn.gelu(product("sd,df->sf", h, w["w_up"], mode), approximate=True)
    return x + product("sf,fd->sd", a, w["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("cfg_key", "mode"))
def _node(layers, x, cfg_key, mode):
    cfg = dict(cfg_key)
    return jax.lax.scan(lambda h, w: (_layer(h, w, cfg, mode), None), x, layers)[0]


def apply_node(params, x, cfg, depth, mode):
    CALLS["apply_node"] += 1
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta")
    return _node(params, x, tuple((k, cfg[k]) for k in keys), mode)


def node_flops(cfg, depth):
    CALLS["node_flops"] += 1
    s, d, f = cfg["seq_len"], cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    params = d * h * hd + 2 * d * hk * hd + h * hd * d + 2 * d * f
    return cfg["layers_per_depth"][depth] * (2.0 * s * params + 2.0 * s * s * h * hd)
'''


def _bench_files():
    return {p.relative_to(BENCH_DIR): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in BENCH_DIR.rglob("*") if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    before = _bench_files()
    tiny = tiny_tree(tmp_path_factory.mktemp("checkout"))
    (tiny.dir / "archs" / "toy_gelu.py").write_text(TOY)
    cfg = json.loads((DATA / "tiny-t4.json").read_text())
    cfg.update(name="tiny-toy", model_type="toy_gelu", hidden_act="gelu",
               architectures=["ToyGeluForCausalLM"])
    (tiny.dir / "configs" / "tiny-toy.json").write_text(json.dumps(cfg))
    (tiny.dir / "checks" / f"{CELL}.json").write_text(json.dumps({"limits": real_limits()}))
    spec = json.loads((tiny.root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-toy", "source": "https://example.org/toy",
                            "file": "bench/configs/tiny-toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-toy", "traffic": "tiny-closed",
                              "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        m["workloads"].append(CELL)
    (tiny.root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Benchmark(root=tiny.root, bench_dir=tiny.dir)
    with on_cpu():
        result, controls = harness.run_cell(bench, CELL, SEED, SECONDS, True,
                                            time.perf_counter(), chip_check=False,
                                            controls=("fp8",))
    return bench, cfg, result, controls, before


def test_a_new_architecture_runs_correct_from_its_files_alone(toy_run):
    bench, cfg, result, _, _ = toy_run
    assert result["correct"] is True and result["attempted"] > 0
    assert result["checks"]["logit_err"]["value"] <= real_limits()["logit_err"]
    calls = bench.arch(cfg).CALLS
    assert calls["model_config"] == 1      # the program was built from the toy's config
    assert calls["init_node"] > 0 and calls["apply_node"] > 0   # the toy's reference
    assert calls["node_flops"] > 0         # the toy's FLOP count, under mfu
    assert result["metrics"]["mfu"]["value"] > 0


def test_the_toy_s_flops_are_its_own(toy_run):
    bench, cfg, *_ = toy_run
    toy = bench.arch(cfg)
    mistral = bench.arch(dict(cfg, model_type="mistral"))
    # A GELU feed-forward has two matrices where SwiGLU has three.
    assert flops.request_flops(toy, cfg, (0,)) < flops.request_flops(mistral, cfg, (0,))


def test_the_toy_s_control_is_not_correct(toy_run):
    assert toy_run[3]["fp8"]["correct"] is False


def test_no_file_of_the_benchmark_s_own_directory_changed(toy_run):
    assert _bench_files() == toy_run[4]
    assert not (BENCH_DIR / "archs" / "toy_gelu.py").exists()
