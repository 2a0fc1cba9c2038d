"""1-vs-n-device sharded panel execution (``repro.parallel.hshard``).

Times the batched H-matrix apply and the fused PCG solve on an (N, R)
panel twice — unsharded on one device, and column-sharded over an
``n_devices``-wide mesh — and records panel throughput (columns/s) plus
the sharded speedup into ``results/shard/``.

The mesh runs over the devices this process already sees; the benchmark
never starts a child process (one holding a chip would fight its parent
for it).  On a host with fewer devices it fails and says how to get
more: on CPU, set ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>``
and ``JAX_PLATFORMS=cpu`` before starting Python.  Fake host devices share
one physical CPU, so a "speedup" there measures dispatch overhead, not
real scaling — the JSON carries ``forced_host_devices`` so readers can
tell.

    PYTHONPATH=src python -m benchmarks.bench_shard [n] [r] [n_devices]
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

from .common import emit, timeit

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "shard")


def run(n: int = 8192, r: int = 64, n_devices: int = 4, c_leaf: int = 128,
        k: int = 16, sigma2: float = 0.5, tol: float = 1e-4,
        max_iter: int = 200) -> dict:
    if jax.device_count() < n_devices:
        raise RuntimeError(
            f"bench_shard needs {n_devices} devices but this process sees "
            f"{jax.device_count()}; on CPU start it with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")

    import numpy as np

    from repro.core import build_hmatrix, halton, make_apply
    from repro.parallel.hshard import make_panel_mesh
    from repro.solve import make_solver

    pts = halton(n, 2)
    X = jnp.asarray(np.random.RandomState(0).randn(n, r).astype(np.float32))
    hm = build_hmatrix(pts, "gaussian", k=k, c_leaf=c_leaf, precompute=True)
    mesh = make_panel_mesh(n_devices)

    record = {"bench": "shard", "n": n, "r": r, "n_devices": n_devices,
              "c_leaf": c_leaf, "k": k, "backend": jax.default_backend(),
              "forced_host_devices": "--xla_force_host_platform_device_count"
              in os.environ.get("XLA_FLAGS", "")}

    # --- apply: 1 device vs column-sharded mesh
    apply_1dev = make_apply(hm)
    t1 = timeit(lambda: apply_1dev(X))
    apply_sharded = make_apply(hm, mesh=mesh)
    tn = timeit(lambda: apply_sharded(X))
    record["apply"] = {
        "t_1dev_s": t1, "t_shard_s": tn,
        "cols_per_sec_1dev": r / t1, "cols_per_sec_shard": r / tn,
        "speedup": t1 / tn}
    emit("shard_apply_1dev", t1, f"cols_per_sec={r / t1:.1f}")
    emit("shard_apply_ndev", tn,
         f"cols_per_sec={r / tn:.1f};speedup_x{t1 / tn:.2f}")

    # --- fused solve: 1 device vs column-sharded mesh
    kw = dict(tol=tol, max_iter=max_iter, precondition=True)
    s1 = make_solver(hm, sigma2, **kw)
    sn = make_solver(hm, sigma2, mesh=mesh, **kw)
    _, info = s1(X)                                     # compile + iter count
    t1s = timeit(lambda: s1(X)[0], warmup=0, iters=1)
    sn(X)                                               # compile
    tns = timeit(lambda: sn(X)[0], warmup=0, iters=1)
    record["solve"] = {
        "iterations": info.iterations, "t_1dev_s": t1s, "t_shard_s": tns,
        "cols_per_sec_1dev": r / t1s, "cols_per_sec_shard": r / tns,
        "speedup": t1s / tns}
    emit("shard_solve_1dev", t1s, f"iters={info.iterations}")
    emit("shard_solve_ndev", tns, f"speedup_x{t1s / tns:.2f}")

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "shard_panel.json"), "w") as f:
        json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:4]]
    run(*args)
