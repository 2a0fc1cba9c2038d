#!/usr/bin/env python3
"""Readings that a cell's limits are set from, for many seeds in one
process (set-up is paid once per seed, compiles once).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control
    python3 bench/calibrate.py --workload <cell> --seeds 1 --rates 100,200

With ``--rates`` (a serving cell) one set-up serves a window of
``--seconds`` at each offered rate in turn and prints its p95 latency,
failures and backlog: the sweep that finds the knee.

Without ``--control`` each seed runs the cell's loop as ``run.py`` does
(set-up, a window of ``--seconds``, release, check) and prints the
compared numbers: the program's readings, whose largest is the lower
reading.  With ``--control`` the plain reference, at the next precision
below the configuration's (``high``: three bfloat16 passes), takes the
library's place under the same loop and the same check (the loop
module's ``control``); the smallest of its readings is the upper reading.
Each control window (the loop's ``control_window``) makes as many calls
as a run compares.  One JSON line per seed; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import suite  # noqa: E402


def make_loop(cell, seed: int):
    return suite.loop(cell.traffic["loop"], cell.root).Loop(cell, seed)


def readings(cell, seed: int, seconds: float, control: bool = False):
    """One seed: set-up, window, release, check; the compared numbers."""
    loop = make_loop(cell, seed)
    loop.setup()
    if control:
        loop.control_window()
    else:
        loop.window(seconds)
    loop.release()
    return {name: value for name, value, _ in loop.check()}, loop.attempted


def sweep(cell, seed: int, seconds: float, rates) -> int:
    """Serve a window at each rate with one set-up; a backlog that grows
    shows as a queue that is still deep when the window closes."""
    import time

    loop = make_loop(cell, seed)
    loop.setup()
    for rate in rates:
        loop.attempted = loop.failed = 0
        t0 = time.perf_counter()
        got = loop.window(seconds, dict(cell.traffic, rate_per_s=rate))
        stats = loop.tenant.stats()
        print(json.dumps({"workload": cell.name, "rate_per_s": rate,
                          **got, "attempted": loop.attempted,
                          "failed": loop.failed,
                          "drain_past_window_s":
                              time.perf_counter() - t0 - seconds,
                          "max_queue_depth": stats["max_queue_depth"],
                          "generator_lag_p95_s":
                              loop.counters["generator_lag_p95_s"]}),
              flush=True)
    loop.release()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", default="",
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    cell = suite.load_cell(args.workload)
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    if args.rates:
        return sweep(cell, int(args.seeds.split(",")[0]), args.seconds,
                     [float(r) for r in args.rates.split(",")])
    if args.control:
        suite.loop(cell.traffic["loop"], cell.root).control(setattr, cell)
    for s in args.seeds.split(","):
        got, attempted = readings(cell, int(s), args.seconds, args.control)
        print(json.dumps({"workload": cell.name, "seed": int(s),
                          "control": args.control, "attempted": attempted,
                          **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
