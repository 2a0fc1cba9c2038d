"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  CPU-sized problem sizes
(the paper's N=2^20+ runs need the target accelerator); the *claims* each
benchmark reproduces are scale-free (convergence shape, complexity
exponent, batching speedup factors).

    PYTHONPATH=src python -m benchmarks.run [--quick | --smoke] [--lint]

``--quick`` shrinks problem sizes for a laptop-scale sweep; ``--smoke``
runs EVERY registered bench at tiny dispatch-check sizes (the CI floor:
does each suite still run end to end and write its record).  ``--lint``
runs the hlint device-discipline scan (`scripts/hlint/run.py`) as a
pre-flight — a host-sync regression is caught in seconds instead of
after an hour of timing runs — and its finding counts land in the
`results/perf_trajectory.json` record alongside per-suite status.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax

from repro.runtime.compile_cache import enable_compile_cache

from . import (bench_batching, bench_build, bench_chaos, bench_compare,
               bench_complexity, bench_convergence, bench_harith,
               bench_matmat, bench_memory, bench_roofline, bench_serve,
               bench_shard, bench_solve, bench_tenancy)


SHARD_DEVICES = 4


class SuiteSkipped(Exception):
    """A suite that cannot run in this process; the message says why."""


def _shard(n: int, r: int) -> dict:
    # the mesh spans the devices this process already sees: with fewer, a
    # "sharded" panel would time one device against itself
    if jax.device_count() < SHARD_DEVICES:
        raise SuiteSkipped(
            f"needs {SHARD_DEVICES} devices, this process sees "
            f"{jax.device_count()} (on CPU: JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={SHARD_DEVICES})")
    return bench_shard.run(n=n, r=r, n_devices=SHARD_DEVICES)


def _suites(args) -> list:
    if args.smoke:
        return [
            ("fig11", lambda: bench_convergence.run(n=512)),
            ("fig12-13", lambda: bench_complexity.run(ns=(1024, 2048),
                                                      c_leaf=128)),
            ("fig14-15", lambda: bench_batching.run(n=2048)),
            ("matmat", lambda: bench_matmat.run(n=1024, rs=(1, 8))),
            ("solve", lambda: bench_solve.run(n=1024, domain=16.0,
                                              c_leaf=128)),
            ("shard", lambda: _shard(n=512, r=8)),
            ("build", lambda: bench_build.run(smoke=True)),
            ("serve", lambda: bench_serve.run(smoke=True)),
            ("tenancy", lambda: bench_tenancy.run(smoke=True)),
            ("chaos", lambda: bench_chaos.run(smoke=True)),
            ("memory", lambda: bench_memory.run(smoke=True)),
            ("harith", lambda: bench_harith.run(smoke=True)),
            ("fig16-17", lambda: bench_compare.run(n=1024)),
            ("roofline", lambda: bench_roofline.run()),
        ]
    return [
        ("fig11", lambda: bench_convergence.run(n=1024 if args.quick else 2048)),
        ("fig12-13", lambda: bench_complexity.run(
            ns=(2048, 4096, 8192) if args.quick else (2048, 4096, 8192, 16384, 32768))),
        ("fig14-15", lambda: bench_batching.run(n=8192 if args.quick else 16384)),
        ("matmat", lambda: bench_matmat.run(n=4096 if args.quick else 8192)),
        ("solve", lambda: bench_solve.run(n=4096, domain=16.0) if args.quick
         else bench_solve.run()),
        ("shard", lambda: _shard(n=2048 if args.quick else 8192,
                                 r=16 if args.quick else 64)),
        ("build", lambda: bench_build.run(n=4096, reps=9) if args.quick
         else bench_build.run()),
        ("serve", lambda: bench_serve.run(smoke=True) if args.quick
         else bench_serve.run()),
        ("tenancy", lambda: bench_tenancy.run(smoke=True) if args.quick
         else bench_tenancy.run()),
        ("chaos", lambda: bench_chaos.run(smoke=True) if args.quick
         else bench_chaos.run()),
        ("memory", lambda: bench_memory.run(smoke=True) if args.quick
         else bench_memory.run()),
        ("harith", lambda: bench_harith.run(n=4096, smoke=False)
         if args.quick else bench_harith.run()),
        ("fig16-17", lambda: bench_compare.run(n=4096 if args.quick else 8192)),
        ("roofline", lambda: bench_roofline.run()),
    ]


_REPO = pathlib.Path(__file__).resolve().parent.parent


def _lint_preflight() -> dict:
    """Run hlint (stdlib subprocess) and return its JSON summary.

    Aborts the benchmark run on any non-baselined finding: timing a tree
    with a device-discipline regression measures the regression, not the
    system.
    """
    proc = subprocess.run(
        [sys.executable, str(_REPO / "scripts" / "hlint" / "run.py"),
         "--json"],
        capture_output=True, text=True, cwd=_REPO)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        print(proc.stdout, file=sys.stderr)
        print(proc.stderr, file=sys.stderr)
        sys.exit(f"# hlint pre-flight failed to produce JSON "
                 f"(exit {proc.returncode})")
    if not report["ok"]:
        for f in report["findings"]:
            print(f"# hlint: {f['path']}:{f['line']} [{f['rule']}] "
                  f"{f['message']}", file=sys.stderr)
        sys.exit("# hlint pre-flight found device-discipline regressions; "
                 "fix them (or baseline with justification) before timing")
    print(f"# hlint pre-flight: clean "
          f"({report['baselined']} baselined finding(s))")
    return report


_HEADLINE_KEYS = ("iterations", "qps", "speedup", "p50_ms", "p95_ms",
                  "nbytes", "t_s", "solve_s", "setup_s", "exponent",
                  "iteration_cut", "solve_speedup", "precond_nbytes",
                  "bytes_per_tenant", "multi_vs_single_qps", "speedup_vs_host")


def _headline(ret) -> dict | None:
    """Flatten a suite's returned record into scalar headline metrics.

    One level of nesting is enough for every registered bench (variant /
    per-tenant sub-dicts); only whitelisted metric keys are kept so the
    trajectory record stays a diffable summary, not a second copy of the
    per-suite JSON artifacts.
    """
    if not isinstance(ret, dict):
        return None
    flat = {}
    for key, val in ret.items():
        if isinstance(val, dict):
            for k2, v2 in val.items():
                if k2 in _HEADLINE_KEYS and isinstance(v2, (int, float, bool)):
                    flat[f"{key}.{k2}"] = round(v2, 6) if isinstance(
                        v2, float) else v2
        elif key in _HEADLINE_KEYS and isinstance(val, (int, float, bool)):
            flat[key] = round(val, 6) if isinstance(val, float) else val
    return flat or None


def _git_commit() -> str | None:
    """Short hash of HEAD, or None outside a git checkout."""
    proc = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                          capture_output=True, text=True, cwd=_REPO)
    return proc.stdout.strip() or None if proc.returncode == 0 else None


def _load_trajectory(path: pathlib.Path) -> list:
    """Read the trajectory history, tolerating the legacy formats.

    Early revisions wrote a single overwritten dict; a corrupt or foreign
    file starts a fresh history rather than aborting a benchmark run.
    """
    try:
        with open(path) as f:
            prior = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []
    if isinstance(prior, list):
        return prior
    if isinstance(prior, dict):
        return [prior]
    return []


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sizes")
    ap.add_argument("--smoke", action="store_true",
                    help="every registered bench at tiny CI sizes")
    ap.add_argument("--lint", action="store_true",
                    help="run the hlint device-discipline scan before "
                         "benchmarking; abort on findings")
    args = ap.parse_args()
    enable_compile_cache()

    lint_report = _lint_preflight() if args.lint else None

    print("name,us_per_call,derived")
    failed, statuses = [], {}
    for name, fn in _suites(args):
        t0 = time.perf_counter()
        try:
            ret = fn()
            statuses[name] = {"status": "ok",
                              "seconds": round(time.perf_counter() - t0, 3)}
            metrics = _headline(ret)
            if metrics:
                # per-bench headline metrics ride in the trajectory record,
                # so a perf regression diffs commit-over-commit without
                # opening the per-suite JSON artifacts
                statuses[name]["metrics"] = metrics
        except SuiteSkipped as e:
            print(f"# {name}: skipped: {e}")
            statuses[name] = {"status": "skipped", "reason": str(e)}
        except Exception:
            failed.append(name)
            statuses[name] = {"status": "failed",
                              "seconds": round(time.perf_counter() - t0, 3)}
            traceback.print_exc()

    # perf-trajectory record: an append-only history the CI can diff
    # run-over-run (suite pass/fail + seconds, keyed by commit).  Each run
    # APPENDS a record rather than overwriting the file, so regressions are
    # visible as a trend across PRs, not just against the last run.
    record = {
        "commit": _git_commit(),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "mode": "smoke" if args.smoke else ("quick" if args.quick else "full"),
        "suites": statuses,
        "hlint": None if lint_report is None else {
            "ok": lint_report["ok"],
            "total_findings": lint_report["total_findings"],
            "baselined": lint_report["baselined"],
        },
    }
    out = _REPO / "results" / "perf_trajectory.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    history = _load_trajectory(out)
    history.append(record)
    with open(out, "w") as f:
        json.dump(history, f, indent=2)
    print(f"# appended record {len(history)} to {out.relative_to(_REPO)}")

    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
