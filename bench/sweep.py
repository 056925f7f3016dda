#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate it sustains.

  python3 bench/sweep.py --workload <open cell> --seed <n> --seconds <s> \\
      --rates 20,30,40 [--max-waits 0.01,0.03]

Sets the cell up once, then offers each rate (for each admission window
``max_wait`` given, else the configuration's) for ``--seconds`` through the
same driver as ``run.py``, and prints one JSON line per point: the offered
and completed rates, latency percentiles of the whole window and of its two
halves, and the backlog left at the close.  A rate is sustained when the
completed rate keeps up with the offered one and the second half's median
latency is not above the first half's by more than half: the queue does not
grow across the window.  The benchmark's own runs never run this; its
outputs fix the rate written in a traffic file, once, by hand.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--max-waits", default="")
    args = ap.parse_args(argv)

    import contextlib

    import jax

    from bench import harness, model
    from bench import traffic as traffic_mod
    from bench.spec import Benchmark
    from repro.serving import AffinityPolicy

    bench = Benchmark()
    cell = bench.workload(args.workload)
    harness.check_devices(cell["chips"])
    cfg, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    if traffic["loop"] != "open":
        raise SystemExit("the sweep is for open-loop cells")
    harness.configure_cache()
    program = model.build_program(cfg, args.seed)
    engine = model.build_engine(program, cfg)
    plan0 = traffic_mod.make_plan(dict(traffic, rate_per_s=max(map(float, args.rates.split(",")))),
                                  args.seed, args.seconds, cfg["seq_len"], cfg["vocab_size"],
                                  len(cfg["num_classes"]))
    prompts = jax.device_put([p[None, :] for p in plan0.prompts])
    harness.warm_up(engine, cfg, traffic_mod.distinct_subsets(traffic), prompts)
    print(json.dumps({"setup_s": time.perf_counter() - T_PROCESS}), flush=True)
    waits = ([float(w) for w in args.max_waits.split(",")] if args.max_waits
             else [float(cfg["policy"]["max_wait_s"])])
    pct = traffic_mod.percentile
    for wait in waits:
        policy = AffinityPolicy(max_group_size=int(cfg["policy"]["max_group_size"]), max_wait=wait)
        for rate in (float(r) for r in args.rates.split(",")):
            plan = traffic_mod.make_plan(dict(traffic, rate_per_s=rate), args.seed, args.seconds,
                                         cfg["seq_len"], cfg["vocab_size"], len(cfg["num_classes"]))
            plan = dataclasses.replace(plan, prompts=plan0.prompts)
            session = engine.session(policy=policy, clock=harness.clock)
            driver = harness.Driver(session, plan, prompts, cfg,
                                    lambda name: contextlib.nullcontext())
            t0 = harness.clock()
            driver.run(t0, t0 + args.seconds, wait)
            close = harness.clock()
            backlog = len(driver.inflight) + session.pending_count()
            driver.finish(close)
            reqs = driver.requests
            half = [r.latency for r in reqs if r.start < t0 + args.seconds / 2]
            rest = [r.latency for r in reqs if r.start >= t0 + args.seconds / 2]
            done = [r for r in reqs if r.done is not None and r.done <= close]
            print(json.dumps({
                "max_wait_s": wait, "offered_per_s": len(reqs) / (close - t0),
                "completed_per_s": len(done) / (close - t0),
                "p50_ms": pct([r.latency for r in reqs], 50) * 1e3,
                "p95_ms": pct([r.latency for r in reqs], 95) * 1e3,
                "p50_first_half_ms": pct(half, 50) * 1e3 if half else None,
                "p50_second_half_ms": pct(rest, 50) * 1e3 if rest else None,
                "backlog_at_close": backlog,
                "late_p95_ms": pct([r.sent - r.due for r in reqs], 95) * 1e3,
                "mean_group": session.requests_admitted / max(session.groups_executed, 1),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
