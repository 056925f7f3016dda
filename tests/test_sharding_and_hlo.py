"""Sharding utilities + the trip-count-aware HLO cost analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_cost import analyze_hlo, collective_breakdown
from repro.sharding.policy import (
    FSDP_TP_POLICY, TP_POLICY, _ambient_mesh, shard_act,
)
from repro.sharding.utils import fit_spec, fit_specs, tree_bytes


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_fit_spec_drops_nondivisible():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # 1 KV head cannot shard over 16 -> replicated on that axis
    assert fit_spec((64, 1, 128), P(None, "model", None), mesh) == P(None, None, None)
    # 48 heads shard fine
    assert fit_spec((64, 48, 128), P(None, "model", None), mesh) == P("model",) or \
        fit_spec((64, 48, 128), P(None, "model", None), mesh) == P(None, "model", None)


def test_fit_spec_tuple_prefix_fallback():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    # batch 32 divides pod*data=32
    assert fit_spec((32, 8), P(("pod", "data"), None), mesh) == P(("pod", "data"), None)
    # batch 2 only divides the ("pod",) prefix
    assert fit_spec((2, 8), P(("pod", "data"), None), mesh) == P(("pod",), None)
    # batch 1 divides nothing
    assert fit_spec((1, 8), P(("pod", "data"), None), mesh) == P(None, None)


def test_fit_specs_tree():
    mesh = _FakeMesh({"data": 4, "model": 4})
    shapes = {"a": jax.ShapeDtypeStruct((8, 12), jnp.float32),
              "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
    specs = {"a": P("data", "model"), "b": P("model")}
    out = fit_specs(shapes, specs, mesh)
    assert out["a"] == P("data", "model")
    assert out["b"] == P(None)


def test_tree_bytes():
    t = {"x": jax.ShapeDtypeStruct((10, 10), jnp.bfloat16),
         "y": jax.ShapeDtypeStruct((5,), jnp.float32)}
    assert tree_bytes(t) == 10 * 10 * 2 + 5 * 4


def test_shard_act_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shard_act(x, TP_POLICY, "batch", "model")
    assert y is x


def test_policy_axis_resolution():
    assert TP_POLICY.physical("batch") == ("pod", "data")
    assert TP_POLICY.physical("fsdp") is None
    assert FSDP_TP_POLICY.physical("fsdp") == "data"
    with pytest.raises(ValueError):
        TP_POLICY.physical("bogus")


def test_param_spec_convention():
    # matrices: first axis -> fsdp (None under TP), last -> model
    assert TP_POLICY.param_spec((8, 8)) == P(None, "model")
    assert FSDP_TP_POLICY.param_spec((8, 8)) == P("data", "model")
    assert TP_POLICY.param_spec((4, 8, 8)) == P(None, None, "model")
    # vectors and scalars replicate
    assert TP_POLICY.param_spec((8,)) == P(None)
    assert FSDP_TP_POLICY.param_spec(()) == P()


def test_data_and_weight_shard_counts():
    mesh = _FakeMesh({"data": 4, "model": 2})
    assert TP_POLICY.data_shards(mesh) == 4
    assert TP_POLICY.weight_shards(mesh) == 2
    assert FSDP_TP_POLICY.data_shards(mesh) == 4
    assert FSDP_TP_POLICY.weight_shards(mesh) == 8
    pod = _FakeMesh({"pod": 2, "data": 4, "model": 2})
    assert TP_POLICY.data_shards(pod) == 8  # batch spans ("pod", "data")
    assert TP_POLICY.data_shards(None) == 1
    assert TP_POLICY.weight_shards(None) == 1


def test_ambient_mesh_propagates_accessor_failures(monkeypatch):
    """Regression: _ambient_mesh used to swallow *every* exception, so a
    broken mesh context silently degraded all specs to replicated.  A
    failing accessor must surface."""
    def boom():
        raise RuntimeError("mesh state corrupted")

    monkeypatch.setattr(
        jax.sharding, "get_abstract_mesh", boom, raising=False
    )
    with pytest.raises(RuntimeError, match="mesh state corrupted"):
        _ambient_mesh()


def test_ambient_mesh_none_without_context():
    assert _ambient_mesh() is None


def test_ambient_mesh_under_set_mesh_drives_specs():
    from jax.sharding import AxisType

    mesh = jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    with jax.set_mesh(mesh):
        ambient = _ambient_mesh()
        assert ambient is not None
        assert ambient.axis_names == ("data", "model")
        # "pod" is absent from this mesh and drops out of the batch axes.
        assert TP_POLICY.spec("batch", "model") == P(("data",), "model")
    assert _ambient_mesh() is None


# ------------------------------------------------------------------ hlo cost

def test_hlo_cost_multiplies_scan_trip_count():
    def f_scan(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    def f_unroll(x, w):
        for _ in range(7):
            x = jnp.tanh(x @ w)
        return x

    sds = (jax.ShapeDtypeStruct((64, 64), jnp.float32),) * 2
    a = analyze_hlo(jax.jit(f_scan).lower(*sds).compile().as_text())
    b = analyze_hlo(jax.jit(f_unroll).lower(*sds).compile().as_text())
    expected = 2 * 64**3 * 7
    assert a["flops"] == expected
    assert b["flops"] == expected


def test_hlo_cost_counts_dot_flops_exactly():
    def f(x, w):
        return x @ w

    sds = (jax.ShapeDtypeStruct((32, 48), jnp.float32),
           jax.ShapeDtypeStruct((48, 16), jnp.float32))
    a = analyze_hlo(jax.jit(f).lower(*sds).compile().as_text())
    assert a["flops"] == 2 * 32 * 48 * 16


def test_hlo_cost_bytes_positive_and_bounded():
    def f(x):
        return jnp.tanh(x) * 2.0

    sds = (jax.ShapeDtypeStruct((256, 256), jnp.float32),)
    a = analyze_hlo(jax.jit(f).lower(*sds).compile().as_text())
    nbytes = 256 * 256 * 4
    assert nbytes <= a["bytes"] <= 6 * nbytes  # in + out (+ copies)
    assert a["collective_bytes"] == 0.0


def test_collective_breakdown_matches_analyze_hlo():
    if jax.device_count() < 2:
        pytest.skip("needs a multi-device host")
    from jax.sharding import Mesh, NamedSharding

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("model",))

    def f(x, w):
        return x @ w

    xs = jax.ShapeDtypeStruct(
        (16, 32), jnp.float32,
        sharding=NamedSharding(mesh, P(None, None)),
    )
    ws = jax.ShapeDtypeStruct(
        (32, 64), jnp.float32,
        sharding=NamedSharding(mesh, P(None, "model")),
    )
    out_sharding = NamedSharding(mesh, P(None, None))
    hlo = (
        jax.jit(f, out_shardings=out_sharding)
        .lower(xs, ws).compile().as_text()
    )
    bd = collective_breakdown(hlo)
    acc = analyze_hlo(hlo)
    assert sum(bd.values()) == acc["collective_bytes"] > 0
    for kind, v in bd.items():
        assert acc[f"coll_{kind}"] == v
