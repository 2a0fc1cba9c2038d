"""Kernel ridge regression with an H-matrix operator + fused CG (paper §1, eq. 1).

Fits a whole FAMILY of targets f_j(y) = sin(a_j y_0) cos(b_j y_1) on one
Halton design, solving (A + sigma^2 I) C = F with ``repro.solve.make_solver``:
the ENTIRE multi-RHS preconditioned CG runs as one jitted ``lax.while_loop``
— per-column alpha/beta, per-column active masks (converged targets freeze
on device; no host sync per iteration), block-Jacobi preconditioning from
the inadmissible diagonal leaf blocks — with every A-product one batched
H-matrix matmat over all targets.

The design lives on a SCALED domain (side ``DOMAIN``), i.e. the kernel
length scale is much smaller than the domain: the regime where H-matrix
near-field actually dominates conditioning and block-Jacobi pays off.

    PYTHONPATH=src python examples/kernel_regression.py
"""
import time

import jax
import jax.numpy as jnp

from repro.core import build_hmatrix, halton, make_apply, sinusoid_targets
from repro.solve import make_solver
from repro.runtime.compile_cache import enable_compile_cache

DOMAIN = 32.0  # domain side length (kernel length scale is 1)


def main():
    enable_compile_cache()
    n, sigma2 = 16384, 1e-2
    pts = halton(n, 2) * DOMAIN
    F = sinusoid_targets(pts, 8, DOMAIN)                      # (N, R)

    t0 = time.perf_counter()
    hm = build_hmatrix(pts, "gaussian", k=16, c_leaf=256, precompute=True)
    print(f"setup: {time.perf_counter() - t0:.2f}s   N={n}  targets={F.shape[1]}")

    solver = make_solver(hm, sigma2, tol=1e-3, max_iter=300, precondition=True)
    t0 = time.perf_counter()
    coef, info = solver(F)
    # the solve and its SolveInfo are lazy: block before stopping the clock
    jax.block_until_ready(coef)
    dt = time.perf_counter() - t0
    print(f"fused PCG: {info.iterations} iterations, {dt:.2f}s incl. compile "
          f"({dt / F.shape[1]:.2f}s amortized per target); "
          f"per-target iterations {info.iters_per_column.tolist()}")

    op = make_apply(hm)
    resid = float(jax.device_get(
        jnp.linalg.norm(op(coef) + sigma2 * coef - F) / jnp.linalg.norm(F)))
    print(f"relative residual: {resid:.2e}")


if __name__ == "__main__":
    main()
