#!/usr/bin/env python3
"""Readings a cell's output limits are set from.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, one run of the cell as ``run.py`` makes it (at the cell's own
sizes and load, with a short window): its sample of served answers is
compared with the float32 reference -- the program's reading -- and so are
the control's answers to the same requests: the reference computed with
every matrix product's inputs rounded to fp8, the precision below the
configuration's bfloat16, put in the program's place.  Both are judged by
the cell's limits; the program has to come out correct and the control not.
A limit lies above every program reading and below every control reading.
Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from bench.harness import run_cell
    from bench.spec import Benchmark

    bench = Benchmark()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, controls = run_cell(bench, args.workload, seed, args.seconds, False,
                                    time.perf_counter(), controls=("fp8",))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "attempted": result["attempted"],
                          "program": {"correct": result["correct"], "checks": result["checks"]},
                          "control": controls["fp8"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
