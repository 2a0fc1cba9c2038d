"""Sharded multi-device panel execution (`repro.parallel.hshard`) vs the
single-device executors, plus the serve-layer panel packing guarantees.

Two ways these tests run:

  * DIRECTLY under a forced multi-device CPU, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — this is what
    the CI shard job does.  On a single device the mesh tests self-skip.
  * Via the ``slow``-marked subprocess test at the bottom, which re-runs
    this file under 4 forced host devices so the plain tier-1 suite
    (``scripts/test.sh``, no XLA flags — see tests/conftest.py) still
    covers the mesh path on any machine.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_hmatrix, halton, make_apply
from repro.parallel.hshard import (make_panel_mesh, make_sharded_apply,
                                   make_sharded_solver, pad_panel_width)
from repro.solve import make_solver

N_DEV = 4
requires_mesh = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason=f"needs >= {N_DEV} devices "
           f"(XLA_FLAGS=--xla_force_host_platform_device_count={N_DEV})")

SIGMA2 = 0.5


def _system(n, rng, r, precompute=True):
    pts = halton(n, 2)
    F = jnp.asarray(rng.randn(n, r).astype(np.float32))
    hm = build_hmatrix(pts, "gaussian", k=16, c_leaf=128,
                       precompute=precompute)
    return hm, F


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (1e-30 + jnp.linalg.norm(b)))


def test_pad_panel_width():
    assert pad_panel_width(8, 4) == 8
    assert pad_panel_width(5, 4) == 8
    assert pad_panel_width(1, 4) == 4
    assert pad_panel_width(0, 4) == 4  # empty panels still shard


@requires_mesh
@pytest.mark.parametrize("shard", ["columns", "rows"])
@pytest.mark.parametrize("r", [8, 5, 1])
@pytest.mark.parametrize("precompute", [True, False])
def test_sharded_apply_matches_single_device(shard, r, precompute, rng):
    """make_apply(mesh) == make_apply() to 1e-5 for both sharding paths,
    P and NP mode, R evenly divisible (8), ragged (5), and single (1)."""
    hm, X = _system(700, rng, r, precompute=precompute)
    mesh = make_panel_mesh(N_DEV)
    z0 = make_apply(hm)(X)
    zs = make_apply(hm, mesh=mesh, shard=shard)(X)
    assert zs.shape == z0.shape
    assert _rel(zs, z0) < 1e-5, (shard, r, precompute)


@requires_mesh
def test_sharded_apply_vector_contract(rng):
    """(N,) operand keeps the vector contract and matches its panel column."""
    hm, X = _system(700, rng, 1)
    mesh = make_panel_mesh(N_DEV)
    for shard in ("columns", "rows"):
        apply_s = make_sharded_apply(hm, mesh, shard=shard)
        z_vec = apply_s(X[:, 0])
        assert z_vec.shape == (700,)
        np.testing.assert_allclose(np.asarray(z_vec),
                                   np.asarray(apply_s(X)[:, 0]),
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):
        make_sharded_apply(hm, mesh)(jnp.zeros(701))
    with pytest.raises(ValueError):
        make_sharded_apply(hm, mesh, shard="diagonal")


@requires_mesh
@pytest.mark.parametrize("precondition", [True, False])
def test_sharded_solver_matches_single_device(precondition, rng):
    """Evenly divisible panel: the column-sharded PCG runs per-column math
    identical to the single-device solver — same solution to 1e-5 and the
    SAME trip count (the psum'd predicate reproduces the global any)."""
    hm, F = _system(700, rng, 8)
    mesh = make_panel_mesh(N_DEV)
    kw = dict(tol=1e-6, max_iter=600, precondition=precondition)
    c0, info0 = make_solver(hm, SIGMA2, **kw)(F)
    cs, infos = make_solver(hm, SIGMA2, mesh=mesh, **kw)(F)
    assert infos.converged
    assert _rel(cs, c0) < 1e-5
    assert infos.iterations == info0.iterations
    np.testing.assert_array_equal(infos.iters_per_column,
                                  info0.iters_per_column)


@requires_mesh
def test_sharded_solver_ragged_panel(rng):
    """R=3 on 4 devices: zero-padded shard columns start converged and the
    sliced result matches the unsharded solve (two independently converged
    CG paths, so tol-scaled agreement as in test_solve)."""
    hm, F = _system(700, rng, 3)
    mesh = make_panel_mesh(N_DEV)
    kw = dict(tol=1e-6, max_iter=600)
    c0, _ = make_solver(hm, SIGMA2, **kw)(F)
    cs, infos = make_sharded_solver(hm, SIGMA2, mesh, **kw)(F)
    assert cs.shape == (700, 3)
    assert infos.iters_per_column.shape == (3,)
    assert infos.residual_norms.shape == (3,)
    assert infos.converged
    np.testing.assert_allclose(np.asarray(cs), np.asarray(c0),
                               rtol=1e-3, atol=1e-4)


@requires_mesh
def test_sharded_solver_single_vector(rng):
    """(N,) rhs pads to one column per device and keeps the vector contract."""
    hm, F = _system(512, rng, 1)
    mesh = make_panel_mesh(N_DEV)
    c_vec, info = make_sharded_solver(hm, SIGMA2, mesh, tol=1e-6,
                                      max_iter=600)(F[:, 0])
    assert c_vec.shape == (512,)
    assert info.converged and info.iters_per_column.shape == (1,)
    c0, _ = make_solver(hm, SIGMA2, tol=1e-6, max_iter=600)(F[:, 0])
    np.testing.assert_allclose(np.asarray(c_vec), np.asarray(c0),
                               rtol=1e-3, atol=1e-4)


@requires_mesh
def test_meshed_servers_match_unmeshed(rng):
    """Servers with a mesh: panel width rounds UP to the device count, a
    load wider than the panel splits (never truncates), and results match
    the single-device servers."""
    from repro.serve.step import HMatrixServer, HMatrixSolveServer
    hm, F = _system(512, rng, 8)
    mesh = make_panel_mesh(N_DEV)

    srv = HMatrixServer(hm, max_batch=6, mesh=mesh)
    assert srv.max_batch == 8                     # rounded up to 4 | width
    queries = [F[:, j] for j in range(8)] + [F[:, 0], F[:, 1], F[:, 2]]
    outs = srv.serve(queries)                     # 11 queries > one panel
    assert len(outs) == len(queries)
    base = make_apply(hm)
    for q, z in zip(queries, outs):
        np.testing.assert_allclose(z, np.asarray(base(q)),
                                   rtol=1e-4, atol=1e-5)

    ssrv = HMatrixSolveServer(hm, SIGMA2, max_batch=3, tol=1e-6,
                              max_iter=600, mesh=mesh)
    assert ssrv.max_batch == 4
    souts = ssrv.serve([F[:, j] for j in range(6)])
    assert len(souts) == 6 and len(ssrv.last_info) == 2
    solver = make_solver(hm, SIGMA2, tol=1e-6, max_iter=600)
    for j, cj in enumerate(souts):
        ref, _ = solver(F[:, j])
        np.testing.assert_allclose(np.asarray(cj), np.asarray(ref),
                                   rtol=1e-2, atol=1e-4)


def test_serve_panel_packing_never_truncates(rng):
    """Single-device regression guard for the serve-layer truncation bug:
    every request batch wider than the panel must SPLIT into extra panels
    with every result returned, and degenerate widths must raise."""
    from repro.serve.step import HMatrixServer, _serve_in_panels
    hm, F = _system(512, rng, 9)
    srv = HMatrixServer(hm, max_batch=4)
    outs = srv.serve([F[:, j] for j in range(9)])  # 9 = 2 full + 1 short panel
    assert len(outs) == 9
    base = make_apply(hm)
    for j in range(9):
        np.testing.assert_allclose(outs[j], np.asarray(base(F[:, j])),
                                   rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError):
        HMatrixServer(hm, max_batch=0)
    with pytest.raises(ValueError):
        _serve_in_panels([np.zeros(512, np.float32)], 512, 0, lambda p: p)


# ---------------------------------------------------------------------------
# Subprocess self-runner: covers the mesh path in the plain tier-1 suite
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(jax.device_count() >= N_DEV,
                    reason="mesh tests already ran directly")
def test_shard_suite_under_forced_devices():
    """Re-run this file under 4 forced host devices (subprocess so the
    device count never leaks into the other tests — see conftest)."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " " if flags else "") + \
        f"--xla_force_host_platform_device_count={N_DEV}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"    # forced devices are host devices
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow", __file__],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    # every mesh test must have RUN in there — none skipped for device count
    assert " passed" in out.stdout and "skipped" not in out.stdout, out.stdout
