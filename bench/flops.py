"""Required FLOPs of a request, and the table of peaks.

A request asks for a subset of the tree's tasks.  What it requires is the
union of the tree nodes on the paths of those tasks, each node once: the
shared prefix runs once however many tasks hang below it.  What one node
requires over one prompt is its architecture's ``node_flops(cfg, depth)``
(``archs/<model_type>.py``), which counts the matmuls each token needs and
causal attention.  Padded rows, recomputed prefixes, attention over padded
keys, the embedding lookup and the task heads (a few kFLOP) are not counted,
so a share of the peak built on this can only understate what the chip
executed.
"""
from __future__ import annotations

from types import ModuleType
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


def request_flops(arch: ModuleType, cfg: Dict, tasks: Iterable[int]) -> float:
    return sum(arch.node_flops(cfg, d) for d, _g in request_nodes(cfg["tree"], tasks))
