"""Serving under fault injection (`repro.serve.faults`): what resilience
costs, and what containment buys.

Two measurements over an apply-backed tenant stack (assembled H-matrix,
compiled panel programs — the real serving path, not an echo stub):

* **Throughput/latency vs fault rate.**  The same request stream served
  under increasing transient-fault rates (all recoverable within the retry
  budget).  Records q/s, p50/p95 per rate, the retry counts, and the
  degradation ratio vs the fault-free run.  The claim: recoverable chaos
  costs retried panels, not failed futures — ``panel_failures`` stays 0 at
  every rate.
* **Breaker isolation overhead.**  A healthy tenant alone vs next to a
  permanently failing neighbor whose breaker trips.  Records the healthy
  tenant's q/s and p95 both ways plus the launch slots the neighbor
  burned (``panel_failures + retries`` from its stats); the claim is
  bounded interference — the dead tenant consumes at most ``threshold``
  launch slots before quarantine.

On CPU the absolute numbers measure dispatch-level behavior (the JSON
carries ``backend``); the claims — zero failed futures under recoverable
chaos, bounded isolation overhead — are scale-free.  JSON lands in
``results/chaos/``.

    PYTHONPATH=src python -m benchmarks.bench_chaos [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from .common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "chaos")


def _percentiles(lat) -> dict:
    lat = np.asarray(lat)
    return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "mean_ms": float(lat.mean() * 1e3)}


def _build_spec(n, max_batch, k, c_leaf):
    from repro.core import build_hmatrix, halton
    from repro.serve.tenancy import apply_tenant
    pts = halton(n, 2)
    hm = build_hmatrix(pts, "gaussian", k=k, c_leaf=c_leaf, precompute=True)
    return apply_tenant(hm, max_batch=max_batch)


def _serve_under_chaos(spec, queries, chaos, reps):
    """Serve the stream under one chaos spec; median-of-reps timing."""
    from repro.serve.faults import ResiliencePolicy, RetryPolicy
    from repro.serve.tenancy import MultiTenantRuntime
    # fast backoff so the benchmark measures retry cost, not sleep choice
    policy = ResiliencePolicy(retry=RetryPolicy(max_attempts=6,
                                                backoff_s=0.0005))
    runs = []
    for _ in range(reps):
        with MultiTenantRuntime(chaos=chaos, resilience=policy) as mtr:
            tenant = mtr.add_tenant("t", spec)
            mtr.precompile()
            t0 = time.perf_counter()
            futs = [(time.monotonic(), tenant.submit(q)) for q in queries]
            mtr.flush()
            lat = []
            for t_submit, f in futs:
                f.result(timeout=600)
                lat.append(time.monotonic() - t_submit)
            t_s = time.perf_counter() - t0
            stats = tenant.stats()
        runs.append({"t_s": t_s, "qps": len(queries) / t_s,
                     "latency": _percentiles(lat),
                     "retries": stats["retries"],
                     "panel_failures": stats["panel_failures"],
                     "faults_injected": stats["faults_injected"]})
    runs.sort(key=lambda r: r["t_s"])
    return runs[len(runs) // 2]


def _isolation(spec, queries, reps):
    """Healthy tenant q/s+p95 alone vs next to a breaker-tripping neighbor."""
    from repro.serve.faults import BreakerPolicy, ResiliencePolicy
    from repro.serve.tenancy import MultiTenantRuntime, TenantSpec

    def broken(panel):
        raise RuntimeError("injected dead neighbor")

    fail_fast = ResiliencePolicy(
        retry=None, breaker=BreakerPolicy(threshold=3, cooldown_s=60.0))

    out = {}
    for mode in ("alone", "with_dead_neighbor"):
        runs = []
        for _ in range(reps):
            with MultiTenantRuntime(chaos="") as mtr:
                good = mtr.add_tenant("good", spec)
                mtr.precompile()
                bad_futs = []
                if mode == "with_dead_neighbor":
                    bad = mtr.add_tenant("bad", TenantSpec(
                        8, 2, broken, resilience=fail_fast))
                    bad_futs = [bad.submit(np.zeros(8, np.float32))
                                for _ in range(12)]
                t0 = time.perf_counter()
                futs = [(time.monotonic(), good.submit(q)) for q in queries]
                mtr.flush()
                lat = []
                for t_submit, f in futs:
                    f.result(timeout=600)
                    lat.append(time.monotonic() - t_submit)
                t_s = time.perf_counter() - t0
                for f in bad_futs:
                    try:
                        f.result(timeout=60)
                    except RuntimeError:
                        pass                        # expected: failed fast
                # launch slots the dead tenant consumed before quarantine
                # (launch_order only records successes, so count from the
                # tenant's own failure/retry stats instead)
                bad_slots = 0
                if mode == "with_dead_neighbor":
                    bs = bad.stats()
                    bad_slots = bs["panel_failures"] + bs["retries"]
            runs.append({"t_s": t_s, "qps": len(queries) / t_s,
                         "latency": _percentiles(lat),
                         "bad_slots": bad_slots})
        runs.sort(key=lambda r: r["t_s"])
        out[mode] = runs[len(runs) // 2]
    out["p95_overhead_x"] = (
        out["with_dead_neighbor"]["latency"]["p95_ms"]
        / max(out["alone"]["latency"]["p95_ms"], 1e-9))
    return out


def run(n: int = 512, max_batch: int = 8, n_requests: int = 256,
        k: int = 16, c_leaf: int = 128, smoke: bool = False) -> dict:
    import jax

    if smoke:
        # 96 requests / max_batch=8 -> 12 panels: enough launches that the
        # seed-40 stream deterministically injects at both nonzero rates
        n, n_requests = 256, 96
    reps = 1 if smoke else 3

    spec = _build_spec(n, max_batch, k, c_leaf)
    rng = np.random.RandomState(2)
    queries = [rng.randn(n).astype(np.float32) for _ in range(n_requests)]

    record = {"bench": "chaos", "n": n, "max_batch": max_batch,
              "n_requests": n_requests, "backend": jax.default_backend(),
              "smoke": smoke, "by_rate": {}}

    # --- throughput/p95 vs recoverable fault rate
    rates = (0.0, 0.05, 0.2)
    base = None
    for rate in rates:
        chaos = ("" if rate == 0.0
                 else f"transient={rate}:1,seed=40")
        r = _serve_under_chaos(spec, queries, chaos, reps)
        if base is None:
            base = r
        r["qps_vs_clean_x"] = r["qps"] / base["qps"]
        record["by_rate"][str(rate)] = r
        emit(f"chaos_transient_{rate}", r["t_s"] / n_requests,
             f"qps={r['qps']:.1f};retries={r['retries']};"
             f"failures={r['panel_failures']};"
             f"p95_ms={r['latency']['p95_ms']:.1f}")

    # --- breaker isolation overhead
    iso = _isolation(spec, queries, reps)
    record["isolation"] = iso
    emit("chaos_isolation_p95_overhead",
         iso["with_dead_neighbor"]["latency"]["p95_ms"] * 1e-3,
         f"alone_p95_ms={iso['alone']['latency']['p95_ms']:.1f};"
         f"overhead_x{iso['p95_overhead_x']:.2f};"
         f"bad_slots={iso['with_dead_neighbor']['bad_slots']}")

    os.makedirs(RESULTS, exist_ok=True)
    out = os.path.join(RESULTS, "chaos_smoke.json" if smoke
                       else "chaos.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI dispatch check)")
    args = ap.parse_args()
    rec = run(smoke=args.smoke)
    # the containment claims, not the timings, gate the exit status
    ok = all(r["panel_failures"] == 0 for r in rec["by_rate"].values())
    ok = ok and rec["isolation"]["with_dead_neighbor"]["bad_slots"] <= 4
    print(f"# chaos: zero failed futures at rates "
          f"{sorted(rec['by_rate'])}, isolation overhead "
          f"x{rec['isolation']['p95_overhead_x']:.2f}")
    if not ok:
        raise SystemExit(1)
