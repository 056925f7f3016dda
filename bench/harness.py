"""One run of one cell: set-up, the measured window, the output check.

Set-up (``setup_s``, from process start to the first due request) points
JAX's persistent compilation cache at the checkout, builds the task tree's
program with its weights made on the device from the seed, opens the
engine under the configuration's policy, and serves warm-up groups that
build every program the traffic can reach, at every batch shape of the
policy, so that nothing compiles inside the window.

The window drives the serving API a user calls: ``engine.session()``,
``submit`` at each request's due time (open loop) or as soon as a client's
previous reply is ready (closed loop), ``step`` on the real clock, and each
response's outputs polled with ``is_ready()``.  A request's latency runs from
its due time (open loop) or its send (closed loop) until every output array
it asked for is ready.  When the window closes nothing more is sent; what was
sent is served to the end, and an answer may come up to a minute late, its
latency counting the wait.  Tails are taken over every request sent in the
window; one never answered counts as infinitely late.

After the window the device's peak memory is read, the program is freed,
and a sample of the served answers is compared with the plain reference
(``compare.py``, ``reference.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from bench import compare, flops, model
from bench import trace as tracing
from bench import traffic as traffic_mod
from bench.reference import Reference
from bench.spec import Benchmark
from repro.launch.compile_cache import configure_compile_cache
from repro.serving import MultitaskRequest

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
LATE_S = 60.0     # an answer may come this long after the close
POLL_S = 0.0005   # host sleep while outputs are in flight
clock = time.perf_counter


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclasses.dataclass(eq=False)
class Request:
    tasks: Tuple[int, ...]
    prompt: int
    start: float                      # latency starts: due time or send time
    sent: float = math.nan
    due: Optional[float] = None       # open loop: host-clock due time
    done: Optional[float] = None      # every output ready; None: never answered
    future: Any = None
    outputs: Optional[Dict[int, Any]] = None
    group_size: int = 0
    flops: float = 0.0

    @property
    def latency(self) -> float:
        return self.done - self.start if self.done is not None else math.inf


@dataclasses.dataclass
class Window:
    """What a per-layer metric reader gets: the window's requests, the
    program's counters over the window, and the trace when one was taken."""

    start: float
    end: float
    requests: List[Request]
    counters: Dict[str, float]
    compiles: int
    chips: int
    peak: Dict
    trace: Optional[tracing.Summary] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def completed(self) -> List[Request]:
        return [r for r in self.requests if r.done is not None and r.done <= self.end]


def check_devices(chips: int) -> List:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    flops.peak(devices[0].device_kind)
    return devices


def configure_cache() -> None:
    """The persistent compilation cache at the checkout's fixed directory,
    holding every program however quickly it compiled (JAX's default leaves
    out those under a second), so that a run after the first builds every
    program from the cache."""
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


@contextlib.contextmanager
def compile_counter():
    """Counts XLA programs built in the block (``count``: compiled or read
    from the persistent cache; ``misses``: compiled because the cache did
    not hold them)."""
    seen = {"count": 0, "misses": 0}

    def built(event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            seen["count"] += 1

    def missed(event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            seen["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(built)
    jax.monitoring.register_event_listener(missed)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(built)
        jax.monitoring.unregister_event_listener(missed)


@contextlib.contextmanager
def gc_pauses():
    """The garbage collector's pauses in the block: how many, their total
    and the longest, in seconds."""
    seen = {"count": 0, "seconds": 0.0, "longest": 0.0}
    began = [0.0]

    def callback(phase: str, _info: Dict) -> None:
        if phase == "start":
            began[0] = clock()
            return
        pause = clock() - began[0]
        seen["count"] += 1
        seen["seconds"] += pause
        seen["longest"] = max(seen["longest"], pause)

    gc.callbacks.append(callback)
    try:
        yield seen
    finally:
        gc.callbacks.remove(callback)


def counters(session, engine) -> Dict[str, float]:
    return {
        "requests_admitted": session.requests_admitted,
        "wait_sum": session.wait_sum,
        "plan_seconds": session.plan_seconds,
        "groups_executed": session.groups_executed,
        "dispatches": engine.executor.dispatch_count,
    }


def serve_group(engine, tasks: Sequence[int], shape: int, prompts) -> None:
    """One full group of ``shape`` requests for ``tasks``, served to the end."""
    session = engine.session(clock=clock)
    futures = [session.submit(MultitaskRequest(x=prompts[j % len(prompts)], tasks=list(tasks)))
               for j in range(shape)]
    session.drain()
    jax.block_until_ready([f.result().outputs for f in futures])


def warm_up(engine, cfg: Dict, subsets: Sequence[Tuple[int, ...]], prompts) -> Dict[str, int]:
    """Serve one full group of each subset at the smallest batch shape, and
    at every other shape one of each subset that built a program there.

    A group runs its tasks in the engine's order filtered to its subset, so
    the programs it needs are (task, resume point) pairs that the batch
    shape does not change: a subset that built none at the first shape finds
    all of them built, at every shape, by the subsets before it.  Where the
    policy re-solves each group's order, every subset is served at every
    shape.  Returns the groups served and the programs built and compiled."""
    needed = list(subsets)
    tally = {"groups": 0, "programs": 0, "compiled": 0}
    for i, shape in enumerate(model.batch_shapes(cfg)):
        builders = []
        for tasks in needed:
            with compile_counter() as built:
                serve_group(engine, tasks, shape, prompts)
            tally["groups"] += 1
            tally["programs"] += built["count"]
            tally["compiled"] += built["misses"]
            if built["count"]:
                builders.append(tasks)
        if i == 0 and not cfg["policy"]["resolve_order_per_plan"]:
            needed = builders
    return tally


class Driver:
    """Sends the plan's requests into a session and watches their outputs."""

    def __init__(self, session, plan: traffic_mod.Plan, prompts, cfg: Dict,
                 span: Callable[[str], Any]):
        self.session, self.plan, self.prompts, self.cfg = session, plan, prompts, cfg
        self.span = span
        self.requests: List[Request] = []
        self.inflight: List[Request] = []
        self._flops: Dict[Tuple[int, ...], float] = {}

    def send(self, tasks: Tuple[int, ...], start: float, due: Optional[float] = None) -> None:
        if tasks not in self._flops:
            self._flops[tasks] = flops.request_flops(self.cfg, tasks)
        r = Request(tasks=tasks, prompt=len(self.requests) % len(self.prompts),
                    start=start, due=due, flops=self._flops[tasks])
        r.sent = clock()
        r.future = self.session.submit(
            MultitaskRequest(x=self.prompts[r.prompt], tasks=list(tasks)))
        self.requests.append(r)
        self.inflight.append(r)

    def poll(self) -> List[Request]:
        finished = []
        for r in self.inflight:
            f = r.future
            if not f.done():
                continue
            if f.error() is None:
                response = f.result()
                if not all(a.is_ready() for a in response.outputs.values()):
                    continue
                r.done, r.outputs, r.group_size = clock(), response.outputs, response.group_size
            finished.append(r)
        if finished:
            gone = {id(r) for r in finished}
            self.inflight = [r for r in self.inflight if id(r) not in gone]
        return finished

    def run(self, t0: float, end: float, max_wait: float) -> None:
        plan, session, span = self.plan, self.session, self.span
        next_due = 0
        if plan.loop == "closed":
            with span("submit"):
                for _ in range(plan.clients):
                    self.send(plan.subsets[len(self.requests) % len(plan.subsets)], clock())
        while True:
            now = clock()
            if now >= end:
                break
            if plan.loop == "open":
                with span("submit"):
                    while next_due < len(plan.due) and t0 + plan.due[next_due] <= now:
                        due = t0 + plan.due[next_due]
                        self.send(plan.subsets[next_due], due, due)
                        next_due += 1
            with span("step"):
                session.step()
            finished = self.poll()
            if plan.loop == "closed" and finished:
                with span("submit"):
                    for _ in finished:
                        if clock() < end:
                            self.send(plan.subsets[len(self.requests) % len(plan.subsets)],
                                      clock())
            now = clock()
            wake = end
            if plan.loop == "open" and next_due < len(plan.due):
                wake = min(wake, t0 + plan.due[next_due])
            if session.pending_count():
                wake = min(wake, session.queue.oldest_arrival() + max_wait)
            if self.inflight:
                wake = min(wake, now + POLL_S)
            if wake > now:
                label = ("wait_outputs" if self.inflight else
                         "wait_admission" if session.pending_count() else "wait_arrival")
                with span(label):
                    time.sleep(wake - now)
        if plan.loop == "open":
            # Requests due in the last instant of the window are still sent.
            while next_due < len(plan.due) and plan.due[next_due] < end - t0:
                due = t0 + plan.due[next_due]
                self.send(plan.subsets[next_due], due, due)
                next_due += 1

    def finish(self, close: float) -> None:
        """Serve what was sent; wait up to a minute past the close."""
        self.session.drain()
        while self.inflight and clock() < close + LATE_S:
            if not self.poll():
                time.sleep(POLL_S)


def end_to_end(window: Window, setup_s: float) -> Dict[str, float]:
    latencies = [r.latency for r in window.requests]
    return {
        "latency_p50_ms": traffic_mod.percentile(latencies, 50) * 1e3,
        "latency_p95_ms": traffic_mod.percentile(latencies, 95) * 1e3,
        "requests_per_s": len(window.completed) / window.seconds,
        "setup_s": setup_s,
    }


def run_cell(bench: Benchmark, workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, chip_check: bool = True,
             controls: Sequence[str] = ()) -> Tuple[Dict, Dict]:
    """One run; returns the result line's object and, for each control
    asked for (a lower precision of the reference), its verdict and the
    numbers compared, as the result line has them."""
    cell = bench.workload(workload)
    if cell["chips"] != 1:
        raise ValueError("this harness serves one-chip cells")
    devices = check_devices(cell["chips"]) if chip_check else jax.devices()
    peak = flops.peak(devices[0].device_kind)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    check = bench.check(workload)
    configure_cache()

    # ---------------------------------------------------------------- set-up
    num_tasks = len(cfg["num_classes"])
    plan = traffic_mod.make_plan(traffic, seed, seconds, cfg["seq_len"],
                                 cfg["vocab_size"], num_tasks)
    program = model.build_program(cfg, seed)
    engine = model.build_engine(program, cfg)
    prompts = jax.device_put([p[None, :] for p in plan.prompts])
    jax.block_until_ready((program.node_params, prompts))
    t_built = clock()
    warm = warm_up(engine, cfg, traffic_mod.distinct_subsets(traffic), prompts)
    print(f"setup: weights made {t_built - t_process:.3f} s after start, "
          f"warm-up took {clock() - t_built:.3f} s: {warm['groups']} groups built "
          f"{warm['programs']} programs, {warm['compiled']} compiled afresh", file=sys.stderr)
    max_wait = float(cfg["policy"]["max_wait_s"])
    session = engine.session(clock=clock)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = ((lambda name: jax.profiler.TraceAnnotation("bench." + name)) if trace
            else (lambda name: contextlib.nullcontext()))
    gc.collect()
    if trace:
        jax.profiler.start_trace(trace_dir)

    # ---------------------------------------------------------------- window
    driver = Driver(session, plan, prompts, cfg, span)
    before = counters(session, engine)
    with compile_counter() as compiles, gc_pauses() as pauses, span("window"):
        t0 = clock()
        driver.run(t0, t0 + seconds, max_wait)
        close = clock()
        after = counters(session, engine)
        compiled = compiles["count"]
        paused = dict(pauses)
    driver.finish(close)
    print(f"window: {compiled} programs built; {paused['count']} garbage collections "
          f"paused {paused['seconds'] * 1e3:.1f} ms, the longest "
          f"{paused['longest'] * 1e3:.1f} ms", file=sys.stderr)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = tracing.reduce(tracing.load_events(tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = Window(
        start=t0, end=close, requests=driver.requests,
        counters={k: after[k] - before[k] for k in after}, compiles=compiled,
        chips=cell["chips"], peak=peak, trace=summary,
    )
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:cell["chips"]]
    )

    # ------------------------------------------------------- output check
    missing = sum(1 for r in driver.requests if r.done is None)
    served = [r for r in driver.requests if r.done is not None]
    sample = compare.choose(served, compare.SAMPLE, seed,
                            lambda v: model.padded_shape(cfg, v))
    answers = [(r, {t: np.asarray(a, np.float32) for t, a in r.outputs.items()})
               for r in sample]
    for r in driver.requests:
        r.future = r.outputs = None
    del program, engine, session, driver, prompts
    gc.collect()
    t_ref = clock()
    ref = Reference(cfg, seed)
    tasks = [r.tasks for r, _ in answers]
    expected = [ref.logits(plan.prompts[r.prompt], r.tasks) for r, _ in answers]
    numbers = compare.numbers([out for _, out in answers], expected, tasks,
                              cfg["num_classes"], missing)
    # Each control is the reference in a lower precision, put in the
    # program's place: it answers the same sampled requests and is judged
    # by the same comparison.
    control_numbers = {
        mode: compare.numbers([ref.logits(plan.prompts[r.prompt], r.tasks, mode)
                               for r, _ in answers], expected, tasks, cfg["num_classes"], 0)
        for mode in controls
    }
    ref.close()
    print(f"reference: {len(answers)} requests in {clock() - t_ref:.3f} s", file=sys.stderr)

    limits = check["limits"]
    correct = compare.verdict(numbers, limits) and bool(answers)

    # ------------------------------------------------------------ result
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    metrics: Dict[str, Dict] = {}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in bench.per_layer(workload):
            value = bench.reader(m["name"])(window)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(window, t0 - t_process)
        for m in bench.end_to_end(workload):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": len(window.requests),
        "failed": missing,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    result["checks"] = compare.checks(numbers, limits)
    return result, {
        mode: {"correct": compare.verdict(n, limits) and bool(answers),
               "checks": compare.checks(n, limits)}
        for mode, n in control_numbers.items()
    }
