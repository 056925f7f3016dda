"""Model-family equivalences: chunked==dense attention, SSD==sequential,
forward == prefill+decode at every step, SWA ring-cache correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import make_config
from repro.models import transformer as T
from repro.models import ssm as S
from repro.models import hybrid as H
from repro.models import encdec as E
from repro.models.cache import EncDecCache, HybridCache, KVCache
from repro.sharding.policy import TP_POLICY

P = TP_POLICY


def _dense_cfg(**kw):
    base = dict(
        name="t", family="dense", num_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=300, dtype="float32",
        param_dtype="float32", remat=False, attn_chunk=16, loss_chunk=32,
    )
    base.update(kw)
    return make_config(**base)


def test_chunked_equals_dense_attention_model_level():
    cfg = _dense_cfg()
    params = T.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 300)
    a, _ = T.forward(params, toks, cfg, P, use_chunked=True)
    b, _ = T.forward(params, toks, cfg, P, use_chunked=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("window", [None, 16])
def test_dense_decode_matches_forward(window):
    cfg = _dense_cfg(sliding_window=window)
    params = T.init(jax.random.PRNGKey(2), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 44), 0, 300)
    full, _ = T.forward(params, toks, cfg, P)
    last, cache = T.prefill(params, toks[:, :40], cfg, P)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full[:, 39]), atol=3e-3, rtol=3e-3
    )
    if window is None:  # grow linear cache for extra steps
        k = jnp.zeros((2, 2, 44, 2, 16))
        v = jnp.zeros_like(k)
        cache = KVCache(
            k=k.at[:, :, :40].set(cache.k), v=v.at[:, :, :40].set(cache.v)
        )
    cl = jnp.asarray(40)
    for t in range(40, 44):
        step, cache = T.decode_step(params, toks[:, t], cache, cl, cfg, P)
        np.testing.assert_allclose(
            np.asarray(step), np.asarray(full[:, t]), atol=3e-3, rtol=3e-3
        )
        cl = cl + 1


def test_moe_decode_matches_forward():
    cfg = make_config(
        name="m", family="moe", num_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=0, vocab_size=300, moe_num_experts=4, moe_top_k=2,
        moe_num_shared_experts=1, moe_d_ff=96, moe_capacity_factor=8.0,
        dtype="float32", param_dtype="float32", remat=False, attn_chunk=16,
    )
    params = T.init(jax.random.PRNGKey(4), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 20), 0, 300)
    full, _ = T.forward(params, toks, cfg, P)
    last, cache = T.prefill(params, toks[:, :19], cfg, P)
    k = jnp.zeros((2, 2, 20, 2, 16))
    v = jnp.zeros_like(k)
    cache = KVCache(k=k.at[:, :, :19].set(cache.k), v=v.at[:, :, :19].set(cache.v))
    step, _ = T.decode_step(params, toks[:, 19], cache, jnp.asarray(19), cfg, P)
    np.testing.assert_allclose(
        np.asarray(step), np.asarray(full[:, 19]), atol=3e-3, rtol=3e-3
    )


def test_ssm_decode_matches_forward():
    cfg = make_config(
        name="s", family="ssm", num_layers=2, d_model=32, n_heads=1,
        n_kv_heads=1, d_ff=0, vocab_size=300, ssm_state=8, ssm_head_dim=8,
        ssm_chunk=8, dtype="float32", param_dtype="float32", remat=False,
    )
    params = S.init(jax.random.PRNGKey(6), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 21), 0, 300)
    full, _ = S.forward(params, toks, cfg, P)
    last, cache = S.prefill(params, toks[:, :18], cfg, P)
    cl = jnp.asarray(18)
    for t in range(18, 21):
        step, cache = S.decode_step(params, toks[:, t], cache, cl, cfg, P)
        np.testing.assert_allclose(
            np.asarray(step), np.asarray(full[:, t]), atol=3e-3, rtol=3e-3
        )
        cl = cl + 1


def test_hybrid_decode_matches_forward():
    cfg = make_config(
        name="h", family="hybrid", num_layers=4, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab_size=300, ssm_state=8, ssm_head_dim=8,
        ssm_chunk=8, hybrid_attn_period=2, dtype="float32",
        param_dtype="float32", remat=False, attn_chunk=8,
    )
    params = H.init(jax.random.PRNGKey(8), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 20), 0, 300)
    full, _ = H.forward(params, toks, cfg, P)
    last, cache = H.prefill(params, toks[:, :19], cfg, P)
    k = jnp.zeros((2, 2, 20, 2, 8))
    v = jnp.zeros_like(k)
    cache = HybridCache(
        ssm=cache.ssm,
        kv=KVCache(k=k.at[:, :, :19].set(cache.kv.k), v=v.at[:, :, :19].set(cache.kv.v)),
    )
    step, _ = H.decode_step(params, toks[:, 19], cache, jnp.asarray(19), cfg, P)
    np.testing.assert_allclose(
        np.asarray(step), np.asarray(full[:, 19]), atol=3e-3, rtol=3e-3
    )


def test_encdec_decode_matches_forward():
    cfg = make_config(
        name="e", family="encdec", num_layers=2, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab_size=300, enc_layers=2, enc_inputs=16,
        activation="gelu", dtype="float32", param_dtype="float32",
        remat=False, attn_chunk=8,
    )
    params = E.init(jax.random.PRNGKey(10), cfg)
    feats = jax.random.normal(jax.random.PRNGKey(11), (2, 24, 16))
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 20), 0, 300)
    full, _ = E.forward(params, feats, toks, cfg, P)
    last, cache = E.prefill(params, feats, toks[:, :19], cfg, P)
    k = jnp.zeros((2, 2, 20, 4, 8))
    v = jnp.zeros_like(k)
    cache = EncDecCache(
        self_kv=KVCache(
            k=k.at[:, :, :19].set(cache.self_kv.k),
            v=v.at[:, :, :19].set(cache.self_kv.v),
        ),
        cross_k=cache.cross_k, cross_v=cache.cross_v,
    )
    step, _ = E.decode_step(params, toks[:, 19], cache, jnp.asarray(19), cfg, P)
    np.testing.assert_allclose(
        np.asarray(step), np.asarray(full[:, 19]), atol=3e-3, rtol=3e-3
    )


# --------------------------------------------------------------------------
# Task-tree blocks (models/multitask.py): per-layer weight buffers
# --------------------------------------------------------------------------

TREE = ([[0, 1]], [[0], [1]])  # two depths of 3 layers each
TREE_SEQ = 16


def _tree_program(jit):
    from repro.core.task_graph import TaskGraph
    from repro.models.multitask import build_transformer_program

    cfg = _dense_cfg(num_layers=6)
    graph = TaskGraph.from_groups(TREE)
    built = {}

    def init(key):
        built["prog"] = build_transformer_program(
            key, graph, cfg, (3, 5), seq_len=TREE_SEQ
        )
        return built["prog"].node_params

    params = (jax.jit(init) if jit else init)(jax.random.PRNGKey(0))
    return cfg, graph, built["prog"], params


def _stacked_draws(cfg, graph, jit):
    """Each node's layers drawn as ``build_transformer_program`` draws them:
    one stacked ``vmap`` draw of the node's 3 layers."""
    def draw(key):
        out = {}
        for node in graph.nodes():
            key, sub = jax.random.split(key)
            out[node] = jax.vmap(lambda k: T._init_layer(k, cfg))(
                jax.random.split(sub, 3))
        return out

    return (jax.jit(draw) if jit else draw)(jax.random.PRNGKey(0))


@pytest.mark.parametrize("jit", [False, True])
def test_task_tree_layers_are_separate_buffers_of_the_stacked_draw(jit):
    cfg, graph, _prog, params = _tree_program(jit)
    draws = _stacked_draws(cfg, graph, jit)
    one = jax.eval_shape(lambda k: T._init_layer(k, cfg), jax.random.PRNGKey(0))
    for node in graph.nodes():
        layers = params[node]["layers"]
        assert isinstance(layers, tuple) and len(layers) == 3
        for i, lp in enumerate(layers):
            assert jax.tree.map(jnp.shape, lp) == jax.tree.map(lambda s: s.shape, one)
            want = jax.tree.map(lambda w: w[i], draws[node])
            for a, b in zip(jax.tree.leaves(lp), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_task_tree_block_matches_a_scan_over_stacked_layers():
    from repro.models import layers as L

    cfg, graph, prog, params = _tree_program(jit=False)
    q_pos = jnp.arange(TREE_SEQ, dtype=jnp.int32)

    def scanned(p, x, depth):
        if depth == 0:
            x = L.embed_tokens(p["embed"], x, cfg, P)
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *p["layers"])

        def body(h, lp):
            return T._layer_apply(lp, h, cfg, P, q_pos)[0], None

        return jax.lax.scan(body, x, stacked)[0]

    x = jax.random.randint(jax.random.PRNGKey(1), (2, TREE_SEQ), 0, 300)
    for d, node in enumerate(graph.path(0)):
        got = jax.jit(prog.block_fns[d])(params[node], x)
        want = jax.jit(scanned, static_argnums=2)(params[node], x, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        x = got
