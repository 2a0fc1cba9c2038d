#!/usr/bin/env python3
"""Smoke run of the H-matrix system's main path on a TPU.

    python chip_smoke.py              # one chip: build, apply, Pallas apply,
                                      # fused PCG, multi-tenant serving
    python chip_smoke.py --chips 4    # four chips: the sharded panel path
                                      # against device 0 alone, nothing else

Deployment: the paper's model problem (``configs/hmatrix_paper.py``, §6):
Halton points in [0, 1]^2, Gaussian kernel, eta = 1.5, fixed ACA rank
k = 16, factors precomputed.  Two cuts from the paper's performance runs:

* ``c_leaf`` = 256, not 2048: at 2048 the dense leaves either materialise
  (B, 2048, 2048) temporaries on the jnp path or exceed the dense-leaf
  kernel's VMEM budget;
* N = 2^18, not 2^20: at 2^18 the low-rank store is 2.7 GiB and the Pallas
  apply peaks at 13.9 GB of the chip's 16 GB (compile-time memory
  analysis), so 2^19 would not fit.

The fused solve and the solve tenant run on the kernel-ridge deployment
of ``examples/kernel_regression.py`` instead: the same Halton design on a
square of side 128, which keeps that example's 16 points per unit area at
N = 2^18.  On the unit square the shifted Gaussian system (sigma^2 =
1e-2) has a condition number near 1e7 and its H-matrix error exceeds the
shift, so float32 PCG does not converge there (on the CPU at N = 2^14 the
relative residual is still 0.66 after 300 iterations).

Each phase prints one line with its seconds, sizes and errors; compile
seconds (which fall when the persistent compilation cache hits) print on
a line of their own before the result.  The last line of a passing run is
``{"ok": true, "device": {...}}``.  Any failure — no TPU, a reference-path
switch set, an error over its tolerance, a Pallas kernel that was asked
for and not emitted, a serving fallback — exits non-zero without it.
Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N = 1 << 18
DIM, KERNEL, ETA, K, C_LEAF = 2, "gaussian", 1.5, 16, 256
R_PANEL, R_SOLVE = 64, 8
N_SAMPLE = 512                  # oracle rows
SIGMA2 = 1e-2
SOLVE_SIDE = 128.0              # kernel-ridge design: 16 points per unit area
SEED = 0

# --- tolerances (relative, Frobenius over the sampled oracle rows) --------
# ACA error of this configuration measured on the CPU in float32 against
# the same dense oracle: 5.2e-7 (panel) and 4.9e-7 (vector) at N = 2^14.
ACA_ERR_CPU = 5.3e-7
# The TPU's default float32 matmul rounds each operand to bfloat16 (unit
# roundoff 2^-9).  The longest product chain of the apply, U (V^T X),
# rounds three operands; one more unit covers the dense-leaf product.
# This is one bf16 pass and no more: a higher default would pass with
# room, a lower one fails (matmul precision itself belongs to ROADMAP S2).
BF16_PASS = 4 * 2.0 ** -9
TOL_APPLY = ACA_ERR_CPU + BF16_PASS
# PCG stops each unit-norm column at ||r|| < TOL_CG.  The true residual
# F - (A + sigma^2 I) C is taken with the same H-matrix A applied at
# HIGHEST, as the PCG's own operator is, so the ACA error does not enter:
# it exceeds TOL_CG only by float32 rounding, in the recurrence and in the
# check, of products of size ||A|| ||C|| <= ||A|| / sigma^2 per unit column.
# ||A|| is about the Gaussian's mass at the design's density of 16 points
# per unit area, 16 pi (its largest row sum), so four roundings come to
# 1.2e-3.  (At the default precision the same check rounds at bfloat16:
# on a v5e it read 1.02 for a solve whose residual at HIGHEST is 1.01e-3.)
TOL_CG = 1e-3
SOLVE_ROUNDING = 4 * 2.0 ** -24 * (math.pi * N / SOLVE_SIDE ** 2) / SIGMA2
TOL_SOLVE = TOL_CG + SOLVE_ROUNDING
# Sharded against single-device: the same float32 arithmetic per column
# (columns) or the same products summed in another order (rows, psum).
TOL_SHARD = 1e-4


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields):
    parts = []
    for k, v in fields.items():
        parts.append(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}")
    print(f"[{phase}] " + " ".join(parts), flush=True)


class CompileMeter:
    """Seconds spent compiling or loading from the persistent cache."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Clock:
    """Wall and compile seconds of one phase."""

    def __init__(self, meter):
        self.meter = meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.seconds
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        self.compile_s = self.meter.seconds - self.c0


def check_environment(chips: int):
    """Phase 1: a TPU, no route to the reference paths, the cache on."""
    switched = [v for v in ("REPRO_FORCE_REF", "REPRO_CHAOS")
                if os.environ.get(v)]
    check(not switched, f"{', '.join(switched)} set: the smoke runs the "
                        "device path only")
    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX found no TPU (platform {devices[0].platform!r})")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    say("1 device", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices),
        jax=jax.__version__, compile_cache=cache_dir)
    return jax, devices


def rel(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def main_one_chip(jax, devices, meter):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (apply_in_tree_order, build_hmatrix_device_report,
                            get_kernel, halton, make_apply)
    from repro.serve.tenancy import (MultiTenantRuntime, apply_tenant,
                                     solve_tenant)
    from repro.solve import make_solver

    dev = devices[0]
    kfn = get_kernel(KERNEL)
    rng = np.random.RandomState(SEED)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
    rows = jnp.asarray(np.sort(rng.choice(N, N_SAMPLE, replace=False)))

    @jax.jit
    def oracle(pts, x):
        # dense rows kernel(pts[S], pts) @ x, all of it at HIGHEST
        with jax.default_matmul_precision("highest"):
            return jnp.dot(kfn(pts[rows], pts), x,
                           precision=jax.lax.Precision.HIGHEST)

    # --- phase 2: build on the device -----------------------------------
    pts = halton(N, DIM)
    build = dict(kernel=KERNEL, k=K, c_leaf=C_LEAF, eta=ETA, precompute=True)
    with Clock(meter) as cold:
        hm, rep = build_hmatrix_device_report(pts, **build)
    store = hm.factors.nbytes()
    del hm                      # one store at a time: 16 GB holds no two
    gc.collect()
    with Clock(meter) as warm:
        hm, rep_w = build_hmatrix_device_report(pts, **build)
    say("2 build", n=N, c_leaf=C_LEAF, k=K, levels=rep.n_levels,
        aca_blocks=rep.num_aca_blocks, dense_blocks=rep.num_dense_blocks,
        cold_s=cold.wall_s, cold_compile_s=cold.compile_s,
        warm_s=warm.wall_s, warm_plan_s=rep_w.plan_s,
        warm_factors_s=rep_w.factors_s, store_bytes=store["total"],
        store_gib=store["total"] / 2 ** 30, peak_bytes=peak_bytes(dev))
    check(rep.retries == 0 and rep.fallback_launches == 0,
          "build took a retry or a fallback launch")

    # --- phase 3: apply (jnp path) against the dense oracle -------------
    X = jax.random.normal(keys[0], (N, R_PANEL), jnp.float32)
    x = jax.random.normal(keys[1], (N,), jnp.float32)
    apply = make_apply(hm)
    with Clock(meter) as cold:
        Z = jax.block_until_ready(apply(X))
    with Clock(meter) as warm:
        Z = jax.block_until_ready(apply(X))
    with Clock(meter) as vec:
        z = jax.block_until_ready(apply(x))
    ref_X, ref_x = oracle(pts, X), oracle(pts, x[:, None])[:, 0]
    err_panel, err_vec = rel(Z[rows], ref_X), rel(z[rows], ref_x)
    say("3 apply", r=R_PANEL, cold_s=cold.wall_s,
        cold_compile_s=cold.compile_s, warm_s=warm.wall_s,
        vec_cold_s=vec.wall_s, err_panel=err_panel, err_vec=err_vec,
        tol=TOL_APPLY, peak_bytes=peak_bytes(dev))
    check(err_panel <= TOL_APPLY, f"apply panel error {err_panel:.3e}")
    check(err_vec <= TOL_APPLY, f"apply vector error {err_vec:.3e}")

    # --- phase 6 (run here, while the unit-square store is resident):
    # the Pallas path, which must emit both kernels ----------------------
    apply_p = make_apply(hm, use_pallas=True)
    # the apply's tree-order body with the points and factors as
    # arguments: jitting a closure over them would bake the store into the
    # program as constants
    text = jax.jit(partial(apply_in_tree_order, hm.tree, hm.plan, hm.kernel,
                           hm.k, True)).lower(
        hm.tree.points, hm.factors, X).as_text()
    n_dense = text.count('kernel_name = "batched_kernel_matmat"')
    n_lowrank = text.count('kernel_name = "batched_lowrank_matmat"')
    check("tpu_custom_call" in text and n_dense == 1 and n_lowrank >= 1,
          f"Pallas apply emitted {n_dense} dense-leaf and {n_lowrank} "
          "low-rank kernels")
    with Clock(meter) as cold:
        Zp = jax.block_until_ready(apply_p(X))
    with Clock(meter) as warm:
        Zp = jax.block_until_ready(apply_p(X))
    err_p, diff_p = rel(Zp[rows], ref_X), rel(Zp, Z)
    say("6 pallas", dense_kernels=n_dense, lowrank_kernels=n_lowrank,
        levels=len(hm.plan.aca_levels), cold_s=cold.wall_s,
        cold_compile_s=cold.compile_s, warm_s=warm.wall_s, err_panel=err_p,
        diff_vs_jnp=diff_p, tol=TOL_APPLY, peak_bytes=peak_bytes(dev))
    check(err_p <= TOL_APPLY, f"Pallas apply error {err_p:.3e}")
    check(diff_p <= 2 * TOL_APPLY, f"Pallas vs jnp apply {diff_p:.3e}")
    del apply, apply_p, Z, Zp, z, hm, ref_X, ref_x
    gc.collect()

    # --- phase 4: fused PCG on the kernel-ridge design -------------------
    pts_s = halton(N, DIM) * SOLVE_SIDE
    with Clock(meter) as b2:
        hm_s, _ = build_hmatrix_device_report(pts_s, **build)
    F = jax.random.normal(keys[2], (N, R_SOLVE), jnp.float32)
    F = F / jnp.linalg.norm(F, axis=0)
    solver = make_solver(hm_s, SIGMA2, tol=TOL_CG)
    with Clock(meter) as cold:
        C, info = solver(F)
        iters = info.iterations
    with Clock(meter) as warm:
        C, info = solver(F)
        iters = info.iterations
    exact = make_apply(hm_s)

    def true_residual(C, F):
        # the precision is read when ``exact`` is traced, i.e. at its call
        with jax.default_matmul_precision("highest"):
            res = F - (exact(C) + SIGMA2 * C)
        return float(jnp.max(jnp.linalg.norm(res, axis=0)
                             / jnp.linalg.norm(F, axis=0)))

    res = true_residual(C, F)
    say("4 solve", r=R_SOLVE, sigma2=SIGMA2, side=SOLVE_SIDE,
        build_s=b2.wall_s, iterations=iters,
        iters_per_column=list(map(int, info.iters_per_column)),
        cold_s=cold.wall_s, cold_compile_s=cold.compile_s,
        warm_s=warm.wall_s, true_rel_residual=res, tol=TOL_SOLVE,
        peak_bytes=peak_bytes(dev))
    check(info.converged, f"PCG stopped at {iters} iterations unconverged")
    check(res <= TOL_SOLVE, f"solve true residual {res:.3e}")

    # --- phase 5: multi-tenant serving ------------------------------------
    Xa = np.asarray(jax.random.normal(keys[3], (N, R_PANEL), jnp.float32))
    Fs = np.asarray(F)
    with Clock(meter) as serve, MultiTenantRuntime(max_inflight=2) as mtr:
        ta = mtr.add_tenant("apply", apply_tenant(
            np.asarray(pts), max_batch=R_PANEL,
            build={"c_leaf": C_LEAF, "k": K}))
        ts = mtr.add_tenant("solve", solve_tenant(
            hm_s, SIGMA2, max_batch=R_SOLVE, tol=TOL_CG))
        fa = [ta.submit(Xa[:, j]) for j in range(R_PANEL)]
        fs = [ts.submit(Fs[:, j]) for j in range(R_SOLVE)]
        za = np.stack([f.result(timeout=1200) for f in fa], axis=1)
        cs = np.stack([f.result(timeout=1200) for f in fs], axis=1)
        stats, sa, ss = mtr.stats(), ta.stats(), ts.stats()
    err_a = rel(jnp.asarray(za)[rows], oracle(pts, jnp.asarray(Xa)))
    res_s = true_residual(jnp.asarray(cs), F)
    counters = {c: stats[c] for c in ("retries", "panel_failures",
                                      "shed_requests")}
    counters["fallback_launches"] = (sa["fallback_launches"]
                                     + ss["fallback_launches"])
    say("5 serve", apply_requests=R_PANEL, solve_requests=R_SOLVE,
        wall_s=serve.wall_s, compile_s=serve.compile_s,
        onboard_s=sa["onboard_s"], panels=stats["panels_launched"],
        err_apply=err_a, tol_apply=TOL_APPLY, solve_rel_residual=res_s,
        tol_solve=TOL_SOLVE, **counters, peak_bytes=peak_bytes(dev))
    check(not any(counters.values()), f"serving counters {counters}")
    check(err_a <= TOL_APPLY, f"apply tenant error {err_a:.3e}")
    check(res_s <= TOL_SOLVE, f"solve tenant residual {res_s:.3e}")


def main_four_chips(jax, devices, meter):
    """The sharded panel path (``parallel/hshard.py``) on a four-device
    mesh, each executor against the same program on device 0 alone."""
    import jax.numpy as jnp

    from repro.core import build_hmatrix_device, halton, make_apply
    from repro.parallel.hshard import make_panel_mesh
    from repro.solve import make_solver

    mesh = make_panel_mesh(4)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 2)
    hm = build_hmatrix_device(halton(N, DIM) * SOLVE_SIDE, KERNEL, k=K,
                              c_leaf=C_LEAF, eta=ETA, precompute=True)
    X = jax.random.normal(keys[0], (N, R_PANEL), jnp.float32)
    Z0 = make_apply(hm)(X)
    for shard in ("columns", "rows"):
        apply = make_apply(hm, mesh=mesh, shard=shard)
        with Clock(meter) as cold:
            Zs = jax.block_until_ready(apply(X))
        with Clock(meter) as warm:
            Zs = jax.block_until_ready(apply(X))
        diff = rel(Zs, Z0)
        say(f"shard apply {shard}", devices=4, n=N, r=R_PANEL,
            cold_s=cold.wall_s, cold_compile_s=cold.compile_s,
            warm_s=warm.wall_s, diff_vs_device0=diff, tol=TOL_SHARD)
        check(diff <= TOL_SHARD, f"{shard}-sharded apply differs {diff:.3e}")
        del apply, Zs
    F = jax.random.normal(keys[1], (N, R_SOLVE), jnp.float32)
    F = F / jnp.linalg.norm(F, axis=0)
    C0, info0 = make_solver(hm, SIGMA2, tol=TOL_CG)(F)
    solver = make_solver(hm, SIGMA2, tol=TOL_CG, mesh=mesh)
    with Clock(meter) as cold:
        Cs, info = solver(F)
        iters = info.iterations
    diff = rel(Cs, C0)
    say("shard solve columns", devices=4, r=R_SOLVE, cold_s=cold.wall_s,
        cold_compile_s=cold.compile_s, iterations=iters,
        iterations_device0=info0.iterations, diff_vs_device0=diff,
        tol=TOL_SHARD)
    check(info.converged, f"sharded PCG unconverged after {iters}")
    check(iters == info0.iterations, "sharded PCG took another trip count")
    check(diff <= TOL_SHARD, f"sharded solve differs {diff:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded panel path on four chips")
    args = ap.parse_args()
    try:
        jax, devices = check_environment(args.chips)
        meter = CompileMeter(jax)
        if args.chips == 4:
            main_four_chips(jax, devices, meter)
        else:
            main_one_chip(jax, devices, meter)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    except Exception:
        traceback.print_exc()
        print("FAIL: exception", flush=True)
        return 1
    print(f"[compile] seconds={meter.seconds:.6g} "
          f"persistent_cache_hits={meter.hits} "
          f"persistent_cache_misses={meter.misses}", flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
