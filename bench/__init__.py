"""Chip benchmark of the multitask serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
serves one cell of ``BENCHMARK.json`` on a TPU through the serving API a user
calls and prints one JSON result line.  Everything that belongs to one
configuration, traffic mix, per-layer metric or output check is a file of its
own, found by its name:

* the configuration file that ``BENCHMARK.json`` names (``configs/<name>.json``):
  backbone sizes as published, the cut, the task tree and the serving policy;
* ``traffic/<traffic>.json`` -- parameters of the one general traffic generator;
* ``metrics/<metric>.py``    -- a reader with ``read(window) -> float | None``;
* ``checks/<cell>.json``     -- the limits of the cell's output comparison.

The yardstick lives here too: the traffic generator (``traffic.py``), the
required-FLOPs function and the table of peaks (``flops.py``), the plain
float32 reference of the configuration (``reference.py``), the comparison that
decides ``correct`` (``compare.py``) and the reduction of a profiler trace to
device busy time, top operations and idle gaps (``trace.py``).
"""
