"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the timed path served
is drawn from the seed and recomputed by the plain reference
(``reference.py``).  The sample always holds the request with the most
tasks, one request for every task head, and one request from every batch
shape the window ran (a padded one where there was one), so that it covers
the embedding, every depth of the tree, every head, and every compiled group
shape; the rest is drawn at random.

Numbers compared, each against the limit of its cell's ``checks`` file:

* ``logit_err``: the largest absolute difference between a served logit and
  the reference's (task heads standardise the last hidden state, so logits
  have the same scale at every size);
* ``wrong_outputs``: sampled requests whose served tasks or shapes differ
  from what was asked, or that hold a value that is not finite (limit 0);
* ``missing``: requests due in the window that never got an answer, failed,
  or did not come within a minute of the close (limit 0).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

from bench.seeds import rng

SAMPLE = 32


def choose(served: Sequence, size: int, seed: int,
           shape_of: Callable[[int], int]) -> List:
    """The requests to recompute.  ``served`` items have ``tasks`` and
    ``group_size``; ``shape_of(group_size)`` is the batch shape they ran at."""
    gen = rng(seed, 5)
    order = [served[i] for i in gen.permutation(len(served))]
    picked: List = []

    def take(pred) -> None:
        for r in order:
            if pred(r):
                if r not in picked:
                    picked.append(r)
                return

    widest = max((len(r.tasks) for r in order), default=0)
    take(lambda r: len(r.tasks) == widest)
    for t in sorted({t for r in order for t in r.tasks}):
        take(lambda r, t=t: t in r.tasks)
    for shape in sorted({shape_of(r.group_size) for r in order}):
        take(lambda r, s=shape: shape_of(r.group_size) == s and r.group_size < s)
        take(lambda r, s=shape: shape_of(r.group_size) == s)
    for r in order:
        if len(picked) >= size:
            break
        if r not in picked:
            picked.append(r)
    return picked


def logit_err(served: Dict[int, np.ndarray], ref: Dict[int, np.ndarray]) -> float:
    return max(float(np.max(np.abs(served[t].reshape(-1) - ref[t].reshape(-1))))
               for t in ref)


def wrong(served: Dict[int, np.ndarray], tasks: Sequence[int], num_classes) -> bool:
    if set(served) != set(tasks):
        return True
    return any(
        served[t].size != num_classes[t] or not np.all(np.isfinite(served[t]))
        for t in tasks
    )


def numbers(outputs: Sequence[Dict[int, np.ndarray]], expected: Sequence[Dict[int, np.ndarray]],
            tasks: Sequence[Sequence[int]], num_classes, missing: int) -> Dict[str, float]:
    """The numbers compared, for answers to the sampled requests against the
    reference's; a wrong answer counts under ``wrong_outputs`` alone."""
    bad = [wrong(out, t, num_classes) for out, t in zip(outputs, tasks)]
    err = max((logit_err(out, exp) for out, exp, b in zip(outputs, expected, bad) if not b),
              default=0.0)
    return {"logit_err": err, "wrong_outputs": sum(bad), "missing": missing}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def checks(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict]:
    """Each number compared beside its limit, as the result line prints them."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
