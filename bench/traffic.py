"""The one general traffic generator: a traffic file's parameters and a seed
in, the requests of one run out.

A traffic file (``traffic/<name>.json``) holds:

* ``loop``: ``"open"`` (independent users sending on a schedule, judged on
  tails) or ``"closed"`` (``clients`` callers that each wait for their
  reply before sending the next request);
* ``rate_per_s`` (open) or ``clients`` (closed);
* ``subsets``: the task subsets requests ask for, as lists of task ids,
  ranked from the most to the least frequent;
* ``weights``: ``"zipf"`` (with ``zipf_s``) or ``"uniform"`` over that list.

Every seed gets the same work in another order, so the spread between runs
measures the system and not the draw.  The pattern is drawn once, from a
fixed stream: the open loop's gaps are the quantiles of the exponential
distribution at the rate, shuffled, and the subsets are the exact shares of
the weights (largest remainder), shuffled.  The seed rotates that cycle, so
each run starts at another point of it, and draws the prompts: random token
ids, one per request.  (A fresh shuffle per seed moved the open loop's
tails by half between seeds, against a few percent between two runs of one
seed: which requests arrive together, not the system, set them.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.seeds import rng

PATTERN = 0  # the stream the arrival and subset pattern is drawn from
# A closed loop's sends are not known ahead; its subset sequence is cut here.
CLOSED_SEQUENCE = 1 << 15
CLOSED_PROMPTS = 1024


@dataclasses.dataclass(frozen=True)
class Plan:
    """What one run sends.

    ``subsets[i]`` is the task subset of the ``i``-th request sent, and its
    prompt is ``prompts[i % len(prompts)]``.  ``due`` (open loop) is each
    request's send time in seconds from the start of the window.
    """

    loop: str
    subsets: Tuple[Tuple[int, ...], ...]
    prompts: np.ndarray
    due: Optional[np.ndarray] = None
    clients: int = 0


def weights(traffic: Dict) -> np.ndarray:
    n = len(traffic["subsets"])
    kind = traffic.get("weights", "uniform")
    if kind == "uniform":
        w = np.ones(n)
    elif kind == "zipf":
        w = 1.0 / np.arange(1, n + 1) ** float(traffic["zipf_s"])
    else:
        raise ValueError(f"unknown subset weights {kind!r}")
    return w / w.sum()


def exact_counts(w: np.ndarray, n: int) -> np.ndarray:
    """``n`` split over the shares ``w`` by largest remainder."""
    raw = w * n
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: n - counts.sum()]] += 1
    return counts


def subset_mix(traffic: Dict, n: int, gen: np.random.Generator) -> List[Tuple[int, ...]]:
    """``n`` subsets in the exact shares of the weights, shuffled."""
    subsets = [tuple(sorted(int(t) for t in s)) for s in traffic["subsets"]]
    counts = exact_counts(weights(traffic), n)
    mix = [s for s, c in zip(subsets, counts) for _ in range(c)]
    return [mix[i] for i in gen.permutation(n)]


def open_gaps(rate: float, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``: the
    exponential quantiles at the midpoints of ``n`` equal bins, shuffled."""
    u = (np.arange(n) + 0.5) / n
    return gen.permutation(-np.log1p(-u) / rate)


def rotate(items: List, seed: int) -> List:
    """The cycle ``items`` entered at the seed's point."""
    k = int(seed) % len(items)
    return items[k:] + items[:k]


def validate(traffic: Dict, num_tasks: int) -> None:
    if traffic["loop"] not in ("open", "closed"):
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    for s in traffic["subsets"]:
        if not s or any(not 0 <= int(t) < num_tasks for t in s):
            raise ValueError(f"subset {s} outside tasks 0..{num_tasks - 1}")


def make_plan(traffic: Dict, seed: int, seconds: float, seq_len: int,
              vocab: int, num_tasks: int) -> Plan:
    validate(traffic, num_tasks)
    if traffic["loop"] == "open":
        n = int(round(float(traffic["rate_per_s"]) * seconds))
        # A cycle of n gaps; the first request is due as the window opens,
        # and the gap that would close the cycle is not waited.
        gaps = np.roll(open_gaps(float(traffic["rate_per_s"]), n, rng(PATTERN, 0)),
                       -(seed % n))
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        subsets = rotate(subset_mix(traffic, n, rng(PATTERN, 1)), seed)
        n_prompts, clients = n, 0
    else:
        clients = int(traffic["clients"])
        n = len(traffic["subsets"])
        # Exact copies of the mix, each shuffled on its own, so that every
        # stretch of sends is close to the mix whatever its length.
        mix = subset_mix(traffic, n, rng(PATTERN, 3))
        copies = rng(PATTERN, 1).permuted(
            np.tile(np.arange(n), (-(-CLOSED_SEQUENCE // n), 1)), axis=1
        )
        subsets = [mix[i] for copy in rotate(list(copies), seed) for i in copy]
        due, n_prompts = None, CLOSED_PROMPTS
    prompts = rng(seed, 2).integers(0, vocab, size=(max(n_prompts, 1), seq_len),
                                    dtype=np.int32)
    return Plan(loop=traffic["loop"], subsets=tuple(subsets), prompts=prompts,
                due=due, clients=clients)


def distinct_subsets(traffic: Dict) -> List[Tuple[int, ...]]:
    """Every subset the mix can send, each once (what set-up must warm)."""
    seen: List[Tuple[int, ...]] = []
    for s in traffic["subsets"]:
        key = tuple(sorted(int(t) for t in s))
        if key not in seen:
            seen.append(key)
    return seen


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it.  ``inf`` stands for a request never served."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
