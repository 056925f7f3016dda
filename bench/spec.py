"""Finds the benchmark's pieces by name.

``BENCHMARK.json`` at the checkout's root declares the cells and metrics.
Each piece lives in a file of its own that is found from a name there, so a
cell, configuration, traffic mix or metric is added by adding files:

* a configuration: the ``file`` its entry in ``configs`` names;
* a traffic mix: ``traffic/<traffic>.json`` under this directory;
* a per-layer metric: ``metrics/<name>.py``, with ``read(window)``;
* a cell's output limits: ``checks/<cell>.json``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Benchmark:
    """``BENCHMARK.json`` and the files it names.

    ``root`` is the checkout holding ``BENCHMARK.json``; ``bench_dir`` the
    directory of traffic mixes, metric readers and checks (this one unless a
    test points elsewhere).
    """

    def __init__(self, root: pathlib.Path = ROOT,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.dir = pathlib.Path(bench_dir) if bench_dir is not None else BENCH_DIR
        self.spec = load_json(self.root / "BENCHMARK.json")

    @staticmethod
    def _named(entries: List[Dict], name: str, what: str) -> Dict:
        for e in entries:
            if e["name"] == name:
                return e
        raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> Dict:
        return self._named(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> Dict:
        entry = self._named(self.spec["configs"], name, "configuration")
        return load_json(self.root / entry["file"])

    def traffic(self, name: str) -> Dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def check(self, workload: str) -> Dict:
        return load_json(self.dir / "checks" / f"{workload}.json")

    @staticmethod
    def _applies(metric: Dict, workload: str) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"] if self._applies(m, workload)]

    def per_layer(self, workload: str) -> List[Dict]:
        reported = {m["name"] for m in self.end_to_end(workload)}
        return [
            m for m in self.spec["per_layer"]
            if self._applies(m, workload) and m["moves"] in reported
        ]

    def reader(self, metric: str) -> Callable:
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        module_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(module_name, path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(f"no metric reader at {path}")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
