"""Chip benchmark of the H-matrix library (see ``BENCHMARK.json`` and ``PERF.md``).

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that decides a number lives
here: the configurations and traffic mixes (data files), the plain
reference that decides ``correct``, the work counts and peaks behind the
roofline shares, and the reduction from profiler traces to metrics.
"""
