"""Required FLOPs of a request, and the table of peaks.

A request asks for a subset of the tree's tasks.  What it requires is the
union of the tree nodes on the paths of those tasks, each node once: the
shared prefix runs once however many tasks hang below it.  Each node is a
range of decoder layers at the configuration's widths; a layer requires its
projection and feed-forward matmuls for every token and causal attention
(half of the score matrix and half of its product with V).  Padded rows,
recomputed prefixes, attention over padded keys, the embedding lookup and the
task heads (a few kFLOP) are not counted, so a share of the peak built on
this can only understate what the chip executed.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Iterable, List, Sequence, Tuple

# Peaks of one chip, keyed by ``jax.Device.device_kind``.  A kind that is not
# here is an error: no number is ever computed against a guessed peak.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB HBM per chip",
    },
}

Node = Tuple[int, Tuple[int, ...]]


def peak(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} has no entry in the table of peaks "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


def canonical_tree(tree: Sequence[Sequence[Sequence[int]]]) -> List[List[Tuple[int, ...]]]:
    """Each depth's groups as sorted tuples, in sorted order."""
    return [sorted(tuple(sorted(g)) for g in depth) for depth in tree]


def path(tree: Sequence[Sequence[Sequence[int]]], task: int) -> List[Node]:
    out = []
    for d, groups in enumerate(canonical_tree(tree)):
        out.append(next((d, g) for g in groups if task in g))
    return out


def request_nodes(tree, tasks: Iterable[int]) -> FrozenSet[Node]:
    return frozenset(n for t in tasks for n in path(tree, t))


def layer_flops(cfg: Dict) -> float:
    """One decoder layer over one prompt of ``seq_len`` tokens."""
    s, d, f = cfg["seq_len"], cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    matmul_params = d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * f
    causal_attention = 2.0 * s * s * h * hd  # QK^T and PV, half the scores each
    return 2.0 * s * matmul_params + causal_attention


def request_flops(cfg: Dict, tasks: Iterable[int]) -> float:
    per_layer = layer_flops(cfg)
    layers = cfg["layers_per_depth"]
    return sum(layers[d] * per_layer for d, _g in request_nodes(cfg["tree"], tasks))
