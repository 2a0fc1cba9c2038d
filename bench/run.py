#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; ``bench/suite.py`` finds their files, and the traffic's
``loop`` names the loop, ``bench/loops/<loop>.py``.  Set-up (from process
start: imports, points, build, executors, one call of every program the
window runs) is ``setup_s``.  The window then runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
its per-layer metrics (``bench/metrics/<name>.py``), the device's busy
time and a breakdown.  Afterwards the library's state is dropped and what
the window produced is compared with the plain reference; ``correct`` is
whether every compared number is within its limit
(``bench/limits/<cell>.json``).

Exits non-zero, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for.  The last line of standard output is
the result as one JSON object; the compared numbers, each beside its
limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import gc                                                   # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402
import traceback                                            # noqa: E402
from dataclasses import dataclass                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoAccelerator(Exception):
    pass


class CompileMeter:
    """Seconds spent compiling or loading from the persistent cache, and
    the cache's hits and misses (``jax.monitoring`` events)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class GcMeter:
    """Pauses of Python's garbage collector (``gc.callbacks``), by
    generation, while it is on."""

    def __init__(self):
        self.pauses, self._t0 = [], None

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        full = [s for g, s in self.pauses if g == 2]
        return (f"gc_pauses={len(self.pauses)} gc_full={len(full)} "
                f"gc_max_ms={1e3 * max((s for _, s in self.pauses), default=0)!r} "
                f"gc_total_ms={1e3 * sum(s for _, s in self.pauses)!r}")


@dataclass
class Run:
    """What a per-layer metric's reader sees."""
    cell: str
    device_kind: str
    cols: int | None                # panel width of the traffic
    shapes: object                  # work.Shapes of the built H-matrix
    counters: dict                  # per-call counters of the loop
    trace: object                   # trace.Reduced of the traced window


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def devices_for(jax, chips: int, require_accelerator: bool):
    devs = jax.devices()
    if require_accelerator and devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if require_accelerator and len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(args, root: str, require_accelerator: bool) -> dict:
    from bench import suite
    cell = suite.load_cell(args.workload, root)
    import jax
    devs = devices_for(jax, cell.chips, require_accelerator)
    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    meter = CompileMeter(jax)
    from bench import trace
    loop = suite.loop(cell.traffic["loop"], root).Loop(cell, args.seed)
    loop.setup()
    setup_s = time.perf_counter() - T_START
    say(f"[setup] setup_s={setup_s!r} compile_s={meter.seconds!r} "
        f"compiles={meter.compiles} cache_hits={meter.hits} "
        f"cache_misses={meter.misses} cache_dir={cache}")

    compiles0, tdir = meter.compiles, None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW), GcMeter() as gcm:
            e2e = loop.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    reduced = None
    if args.trace:
        try:
            reduced = trace.reduce(trace.load(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    say(f"[window] compiles_inside={meter.compiles - compiles0} "
        f"attempted={loop.attempted} failed={loop.failed} {gcm.summary()} "
        + " ".join(f"{k}={v!r}" for k, v in e2e.items())
        + "".join(f" {k}={v!r}" for k, v in loop.counters.items()
                  if isinstance(v, (int, float))))
    memory_peak = peak_bytes(devs[:cell.chips])
    loop.release()

    t = time.perf_counter()
    checks = loop.check()
    say(f"[check] seconds={time.perf_counter() - t!r}")

    metrics = {}
    if not args.trace:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        rec = Run(cell=cell.name, device_kind=devs[0].device_kind,
                  cols=cell.traffic.get("cols"), shapes=loop.shape,
                  counters=loop.counters, trace=reduced)
        for m in cell.per_layer:
            got = suite.reader(m["name"], root)(rec)
            if got is None:
                continue
            value, extra = got if isinstance(got, tuple) else (got, {})
            metrics[m["name"]] = {"value": value, "unit": m["unit"], **extra}

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": loop.attempted, "failed": loop.failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    if loop.failed:
        result["correct"] = False
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None, *, root: str = ROOT,
         require_accelerator: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run(args, root, require_accelerator)
    except NoAccelerator as e:
        say(f"FAIL: {e}")
        return 2
    except Exception:
        traceback.print_exc()
        say("FAIL: exception")
        return 1
    for name, c in result["checks"].items():
        say(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
