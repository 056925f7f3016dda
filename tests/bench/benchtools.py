"""A benchmark tree for the CPU tests of ``bench/``: the real architectures
and metric readers and a tiny copy of the ``nemo12b-t4`` deployment, laid out
as a checkout."""
import contextlib
import json
import pathlib
import shutil

import jax

from bench import flops, harness
from bench.spec import BENCH_DIR, ROOT, Benchmark

DATA = pathlib.Path(__file__).resolve().parent / "data"
LIMIT_CELL = "nemo12b-t4.mixed-open"

# A per-layer reader that writes what it was handed next to itself, so that a
# test sees the window a reader gets through the harness's own path.
PROBE = '''"""Test probe: records what a reader is handed."""
import json
import pathlib

COUNTERS = {"dispatches_seen": "engine.executor.dispatch_count",
            "absent": "engine.executor.no_such_counter"}


def read(window):
    spans = window.spans
    seen = {
        "counters": window.counters,
        "cfg": window.cfg.get("name"),
        "arch": window.arch.__name__ if window.arch is not None else None,
        "arch_model_config": callable(getattr(window.arch, "model_config", None)),
        "spans": None if spans is None else {n: s.count for n, s in spans.spans.items()},
        "op_seconds": None if window.trace is None else window.trace.op_seconds,
    }
    pathlib.Path(__file__).with_suffix(".seen.json").write_text(json.dumps(seen))
    return 1.0
'''


def real_limits():
    return json.loads((BENCH_DIR / "checks" / f"{LIMIT_CELL}.json").read_text())["limits"]


def tiny_tree(root: pathlib.Path, extra_metric: bool = False, probe: bool = False) -> Benchmark:
    """``root`` made into a checkout holding one tiny config, an open and a
    closed traffic mix, the real architectures and metric readers, and the
    real cell's limits.  ``probe`` adds the reader ``window_probe``, which
    leaves what it was handed in ``probe_seen(bench)``."""
    bench_dir = root / "bench"
    shutil.copytree(BENCH_DIR / "metrics", bench_dir / "metrics")
    shutil.copytree(BENCH_DIR / "archs", bench_dir / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "traffic").mkdir()
    (bench_dir / "checks").mkdir()
    (bench_dir / "configs").mkdir()
    shutil.copy(DATA / "tiny-t4.json", bench_dir / "configs" / "tiny-t4.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-t4", "source": "https://example.org/tiny",
                        "file": "bench/configs/tiny-t4.json", "reduced": [], "why": "test"}]
    spec["workloads"] = []
    for loop in ("open", "closed"):
        shutil.copy(DATA / f"tiny-{loop}.json", bench_dir / "traffic" / f"tiny-{loop}.json")
        name = f"tiny-t4.{loop}"
        spec["workloads"].append({"name": name, "config": "tiny-t4",
                                  "traffic": f"tiny-{loop}", "chips": 1, "why": "test"})
        (bench_dir / "checks" / f"{name}.json").write_text(
            json.dumps({"limits": real_limits()}))
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["end_to_end"]:
        m.pop("workloads", None)
    for m in spec["per_layer"]:
        m["workloads"] = [c for c in cells if m["name"] != "generator_late_ms" or "open" in c]
    if extra_metric:
        (bench_dir / "metrics" / "requests_done.py").write_text(
            "def read(window):\n    return float(len(window.completed))\n")
        spec["per_layer"].append({"name": "requests_done", "unit": "requests",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "load generator", "moves": "requests_per_s",
                                  "workloads": cells})
    if probe:
        (bench_dir / "metrics" / "window_probe.py").write_text(PROBE)
        spec["per_layer"].append({"name": "window_probe", "unit": "runs",
                                  "better": "higher", "source": "program_span",
                                  "layer": "test", "moves": "requests_per_s",
                                  "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return Benchmark(root=root, bench_dir=bench_dir)


def probe_seen(bench: Benchmark) -> dict:
    """What ``window_probe`` was handed in the last traced run."""
    return json.loads((bench.dir / "metrics" / "window_probe.seen.json").read_text())


@contextlib.contextmanager
def on_cpu():
    """Let the harness run on this CPU: a stand-in peak for its device kind,
    and the persistent compilation cache left as the test process has it."""
    kind = jax.devices()[0].device_kind
    configure = harness.configure_cache
    flops.PEAKS[kind] = dict(flops.PEAKS["TPU v5 lite"], source="test stand-in")
    harness.configure_cache = lambda: None
    try:
        yield
    finally:
        del flops.PEAKS[kind]
        harness.configure_cache = configure
