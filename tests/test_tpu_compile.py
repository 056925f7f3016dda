"""Compiles for a described TPU v5e chip, at real widths, without a chip.

The TPU compiler is installed even where no chip is attached: it refuses a
kernel tiling Mosaic cannot lower and a program that does not fit HBM,
which interpret-mode tests cannot see.  The topology is described inside a
module-scoped fixture (never while a module is imported: only one process
may load the TPU library, and every xdist worker imports every test file).
All such tests live in this one file so that one worker owns the library.
"""
import collections
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

import chip_smoke
from repro.configs.mamba2_780m import CONFIG as MAMBA2
from repro.core.executor import TaskGraphExecutor
from repro.core.task_graph import TaskGraph
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pearson_affinity import pearson_dissimilarity
from repro.kernels.ssd_scan import ssd_scan
from repro.models.multitask import build_transformer_program
from repro.models.transformer import _init_layer
from repro.sharding.policy import TP_POLICY
from repro.sharding.utils import fit_spec

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _smoke_program_shapes():
    """``chip_smoke.py``'s full-width program with parameter shapes only."""
    graph = TaskGraph.from_groups(chip_smoke.GROUPS)
    built = {}

    def init():
        built["prog"] = build_transformer_program(
            jax.random.PRNGKey(0), graph, chip_smoke.full_width_config(),
            chip_smoke.NUM_CLASSES, seq_len=chip_smoke.SEQ_LEN,
        )
        return built["prog"].node_params, built["prog"].head_params

    node_sds, head_sds = jax.eval_shape(init)
    return dataclasses.replace(
        built["prog"], node_params=node_sds, head_params=head_sds
    )


# Task 0 from depth 0 runs the whole path: embedding and all 6 layers.
TASK, RESUME = 0, 0
X_SHAPE = (4, 1, chip_smoke.SEQ_LEN)  # a group of 4 requests


def _path_params(prog):
    path = prog.graph.path(TASK)
    return tuple(
        prog.node_params[path[d]] for d in range(RESUME, prog.graph.depth)
    )


def test_fused_suffix_compiles_at_full_width(one_chip):
    """The executor's own fused program for one task of the smoke's tree:
    mistral-nemo-12b at published widths, 6 layers over three depths,
    S=512, in bf16, on one chip."""
    prog = _smoke_program_shapes()
    ex = TaskGraphExecutor(prog)
    fn, mode = ex._fused_fn(TASK, RESUME, True, X_SHAPE, jnp.int32)
    assert mode == "unrolled"
    params = _path_params(prog)
    compiled = fn.lower(
        _placed(params, one_chip),
        _placed(prog.head_params[TASK], one_chip),
        _sds(X_SHAPE, jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    weights = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights > 4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_sharded_fused_suffix_compiles_on_four_chips(topo):
    """The same program as the smoke's ``--chips 4`` phase: a (1, 4)
    data x model mesh, parameters laid out by ``TP_POLICY``."""
    mesh = Mesh(
        np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    prog = _smoke_program_shapes()
    ex = TaskGraphExecutor(prog, mesh=mesh, sharding=TP_POLICY)
    fn, _mode = ex._fused_fn(TASK, RESUME, True, X_SHAPE, jnp.int32)

    def laid_out(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, NamedSharding(
            mesh, fit_spec(s.shape, TP_POLICY.param_spec(s.shape), mesh)
        )), tree)

    params = _path_params(prog)
    compiled = fn.lower(
        laid_out(params), laid_out(prog.head_params[TASK]),
        _sds(X_SHAPE, jnp.int32, ex._batch_sharding(X_SHAPE, True)),
    ).compile()
    mem = compiled.memory_analysis()
    weights = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(params))
    # Each chip holds about a quarter of the path's weights.
    assert weights / 4 <= mem.argument_size_in_bytes < weights / 3
    assert "all-reduce" in compiled.as_text()


# One HLO instruction: its name, result type, opcode and the rest of the line.
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?(\S+)\s*=\s*(.+?)\s([a-z][\w-]*)\((.*)$")


def _computations(hlo):
    """The instruction lines of each computation of an HLO text, by name
    (``ENTRY`` for the entry computation)."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith((" ", "HloModule")):
            name = "ENTRY" if line.startswith("ENTRY") else line.split()[0].lstrip("%")
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _weight_writes(hlo, weight_shapes):
    """Every top-level instruction of ENTRY and of each loop body that
    writes a buffer shaped like a layer weight, with or without a leading
    layer axis of 1: ``(opcode, dims, op_name)`` of each copy, slice,
    dynamic-slice and loop fusion."""
    comps = _computations(hlo)
    found = []
    for name in ["ENTRY", *re.findall(r"body=%?([\w.\-]+)", hlo)]:
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            _, result, op, rest = m.groups()
            if op not in ("copy", "slice", "dynamic-slice") and not (
                op == "fusion" and "kind=kLoop" in rest
            ):
                continue
            source = re.search(r'op_name="([^"]*)"', rest)
            for dims in re.findall(r"\w+\[([\d,]*)\]", result):
                shape = tuple(int(d) for d in dims.split(",") if d)
                if shape in weight_shapes or (
                    shape[:1] == (1,) and shape[1:] in weight_shapes
                ):
                    found.append((op, shape, source and source.group(1)))
    return found


@pytest.mark.parametrize("resume, rows", [(2, 1), (0, 16)])
def test_fused_suffix_reads_layer_weights_in_place(one_chip, resume, rows):
    """The smoke's tree has 2 layers a depth, as the benchmark's t4
    configuration: its fused suffix runs the layers straight, reading each
    weight from its own buffer, with no loop over stacked layers and no
    slice of a weight written to memory before the matmuls that read it."""
    prog = _smoke_program_shapes()
    ex = TaskGraphExecutor(prog)
    path = prog.graph.path(TASK)
    x = jax.ShapeDtypeStruct((rows, 1, chip_smoke.SEQ_LEN), jnp.int32)
    for d in range(resume):
        x = jax.eval_shape(jax.vmap(prog.block_fns[d], in_axes=(None, 0)),
                           prog.node_params[path[d]], x)
    fn, _mode = ex._fused_fn(TASK, resume, True, x.shape, x.dtype)
    params = tuple(
        prog.node_params[path[d]] for d in range(resume, prog.graph.depth)
    )
    hlo = fn.lower(
        _placed(params, one_chip),
        _placed(prog.head_params[TASK], one_chip),
        _sds(x.shape, x.dtype, one_chip),
    ).compile().as_text()
    assert not re.search(r"\swhile\(", hlo)
    layer = jax.eval_shape(lambda k: _init_layer(k, chip_smoke.full_width_config()),
                           jax.random.PRNGKey(0))
    shapes = {tuple(w.shape) for w in jax.tree.leaves(layer) if w.ndim >= 2}
    writes = _weight_writes(hlo, shapes)
    # A slice of a weight, or a copy carrying a layer axis, is what a loop
    # over stacked layers costs.
    assert [w for w in writes if w[0] != "copy" or w[1][0] == 1] == []
    if rows == 1:
        assert writes == []
    else:
        # At 16 rows the compiler lays some matmul operands out afresh, on
        # either layout of the tree: at most once per argument buffer.
        sources = collections.Counter(w[2] for w in writes)
        assert all(s and s.startswith("params_tuple") for s in sources)
        assert max(sources.values(), default=0) <= 1


def test_flash_attention_compiles(one_chip):
    q = _sds((32, 4096, 128), jnp.bfloat16, one_chip)
    hlo = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pearson_dissimilarity_compiles(one_chip):
    z = _sds((256, 2048), jnp.float32, one_chip)
    hlo = jax.jit(
        lambda z: pearson_dissimilarity(z, interpret=False)
    ).lower(z).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    b, s, n = 1, 4096, MAMBA2.ssm_state
    h, p = MAMBA2.ssm_n_heads, MAMBA2.ssm_head_dim
    f32 = jnp.float32
    args = (
        _sds((b, s, h, p), f32, one_chip), _sds((b, s, h), f32, one_chip),
        _sds((h,), f32, one_chip), _sds((b, s, n), f32, one_chip),
        _sds((b, s, n), f32, one_chip),
    )
    hlo = jax.jit(
        lambda *a: ssd_scan(*a, chunk=128, interpret=False)
    ).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
