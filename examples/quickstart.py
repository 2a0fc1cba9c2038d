"""Quickstart: build an H-matrix and run the fast matvec (the paper's core).

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (build_hmatrix, dense_matvec_oracle, halton,
                        make_matvec)
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    n, d = 8192, 2
    print(f"Halton point set: N={n}, d={d}, Gaussian kernel")
    pts = halton(n, d)

    t0 = time.perf_counter()
    hm = build_hmatrix(pts, kernel="gaussian", k=16, c_leaf=256, eta=1.5)
    print(f"H-matrix setup: {time.perf_counter() - t0:.3f}s  "
          f"({hm.plan.num_aca_blocks} low-rank blocks, "
          f"{hm.plan.num_dense_blocks} dense blocks)")

    matvec = make_matvec(hm)
    x = jnp.asarray(np.random.RandomState(0).randn(n).astype(np.float32))
    matvec(x)  # compile
    t0 = time.perf_counter()
    z = matvec(x).block_until_ready()
    print(f"H-matvec: {time.perf_counter() - t0 :.4f}s "
          f"(vs O(N^2) dense product)")

    z_ref = dense_matvec_oracle(pts, "gaussian", x)
    rel = float(jax.device_get(
        jnp.linalg.norm(z - z_ref) / jnp.linalg.norm(z_ref)))
    print(f"relative error vs dense oracle: {rel:.2e}")

    rep = hm.memory_report()
    print(f"metadata bytes: {rep['meta_bytes']:,}  "
          f"dense-equivalent: {rep['dense_equivalent_bytes']:,}")


if __name__ == "__main__":
    main()
