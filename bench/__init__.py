"""Chip benchmark of the embedding server (see ``BENCHMARK.json``).

Everything the benchmark measures against lives here: the traffic
generator (:mod:`bench.gen`), the plain reference that decides
``correct`` (:mod:`bench.reference`), the reduction from a profiler trace
to device metrics (:mod:`bench.trace`), the table of chip peaks
(``peaks.json``), one file per configuration (``configs/``), per traffic
mix (``traffic/``), per traffic driver (``drivers/``) and per per-layer
metric (``metrics/``).  ``python3 bench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>`` runs one cell once.
"""
