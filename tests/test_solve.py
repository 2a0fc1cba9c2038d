"""Fused multi-RHS H-matrix solve (`repro.solve`) vs dense/host-loop oracles,
plus the block-Jacobi Pallas kernel trio vs its ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_hmatrix, dense_kernel_matrix, diagonal_blocks, halton, make_apply
from repro.kernels.batched_block_solve.ops import (batched_block_cholesky,
                                                   batched_block_cholesky_solve)
from repro.kernels.batched_block_solve.ref import (batched_block_cholesky_ref,
                                                   batched_block_cholesky_solve_ref)
from repro.solve import host_loop_cg, make_solver

SIGMA2 = 0.5  # well-conditioned regularisation for the oracle comparisons


def _system(n, kernel, rng, r, seed_scale=1.0):
    pts = halton(n, 2) * seed_scale
    F = jnp.asarray(rng.randn(n, r).astype(np.float32))
    hm = build_hmatrix(pts, kernel, k=16, c_leaf=128, precompute=True)
    return pts, hm, F


@pytest.mark.parametrize("kernel", ["gaussian", "matern"])
@pytest.mark.parametrize("r", [1, 8])
@pytest.mark.parametrize("precondition", [False, True])
def test_solver_matches_dense_oracle(kernel, r, precondition, rng):
    """make_solver == jnp.linalg.solve up to the H-matrix approximation,
    with and without preconditioning, both kernels, n not a power of two
    (exercises the padded-tail masking)."""
    n = 700
    pts, hm, F = _system(n, kernel, rng, r)
    solver = make_solver(hm, SIGMA2, tol=1e-6, max_iter=600,
                         precondition=precondition)
    C, info = solver(F)
    assert C.shape == (n, r)
    assert info.converged and info.iterations < 600
    A = dense_kernel_matrix(pts, kernel) + SIGMA2 * jnp.eye(n)
    C_ref = jnp.linalg.solve(A, F)
    rel = float(jnp.linalg.norm(C - C_ref) / jnp.linalg.norm(C_ref))
    assert rel < 2e-2, rel


def test_solver_np_mode_matches_p_mode(rng):
    """NP mode (ACA factors regenerated inside the while_loop body) solves
    the same system as P mode (stored factors)."""
    n = 512
    pts = halton(n, 2)
    F = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    hm_np = build_hmatrix(pts, "gaussian", k=16, c_leaf=128, precompute=False)
    hm_p = build_hmatrix(pts, "gaussian", k=16, c_leaf=128, precompute=True)
    assert hm_np.factors is None
    c_np, info_np = make_solver(hm_np, SIGMA2, tol=1e-6, max_iter=400)(F)
    c_p, _ = make_solver(hm_p, SIGMA2, tol=1e-6, max_iter=400)(F)
    assert info_np.converged
    np.testing.assert_allclose(np.asarray(c_np), np.asarray(c_p),
                               rtol=1e-3, atol=1e-4)


def test_solver_single_vector_shape(rng):
    """(N,) rhs keeps the vector contract and matches its own panel column."""
    n = 512
    pts, hm, F = _system(n, "gaussian", rng, 1)
    solver = make_solver(hm, SIGMA2, tol=1e-6, max_iter=400)
    c_vec, _ = solver(F[:, 0])
    c_panel, _ = solver(F)
    assert c_vec.shape == (n,)
    np.testing.assert_allclose(np.asarray(c_vec), np.asarray(c_panel[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_active_mask_cg_matches_host_loop(rng):
    """The fused while_loop CG (no preconditioner) agrees with the host-loop
    CG at loose tolerance: both reach ||r|| < tol, so the solutions agree to
    O(kappa * tol), and the slowest column drives both termination rules
    identically.

    The trip counts are compared in float64.  tol = 1e-6 is 4e-8 of each
    column's norm (about 26), under float32's unit roundoff: there the two
    float32 loops' residual norms swing by up to a decade from one trip to
    the next, and the trip on which every column is first under tol is
    decided by rounding.  Over 16 right-hand-side draws the float32 trips
    were within one of each other for 10 draws with squared distances
    taken as |a|^2 + |b|^2 - 2 a.b and 11 with summed coordinate
    differences; the float64 trips for all 16, with either."""
    n, tol = 700, 1e-6
    F = rng.randn(n, 8).astype(np.float32)
    kw = dict(tol=tol, max_iter=600)
    for dtype in (jnp.float32, jnp.float64):
        with jax.enable_x64(dtype == jnp.float64):
            Fd = jnp.asarray(F, dtype)
            hm = build_hmatrix(halton(n, 2, dtype=dtype), "gaussian", k=16,
                               c_leaf=128, precompute=True)
            C, info = make_solver(hm, SIGMA2, precondition=False, **kw)(Fd)
            ap = make_apply(hm)
            op = lambda v: ap(v) + SIGMA2 * v  # noqa: E731
            C_host, it_host = host_loop_cg(op, Fd, **kw)
            assert C.dtype == dtype
            # per-column freezing means early-converged columns stop
            # refining, so allow a loose (tol-scaled) disagreement rather
            # than bit equality
            np.testing.assert_allclose(np.asarray(C), np.asarray(C_host),
                                       rtol=1e-3, atol=1e-4)
            assert info.converged      # every column froze under tol
            if dtype == jnp.float64:
                assert abs(info.iterations - it_host) <= 1, (
                    info.iterations, it_host)


def test_active_mask_freezes_converged_columns(rng):
    """A zero rhs column is converged at entry: it stays exactly zero and
    records zero iterations while other columns keep iterating."""
    n = 512
    pts, hm, F = _system(n, "gaussian", rng, 4)
    F = F.at[:, 2].set(0.0)
    solver = make_solver(hm, SIGMA2, tol=1e-6, max_iter=400)
    C, info = solver(F)
    assert float(jnp.abs(C[:, 2]).max()) == 0.0
    assert info.iters_per_column[2] == 0
    assert info.iterations == info.iters_per_column.max()
    assert (info.iters_per_column[[0, 1, 3]] > 0).all()


def test_preconditioner_reduces_iterations(rng):
    """Block-Jacobi cuts CG iterations on a localized-kernel system (kernel
    length scale << domain: conditioning dominated by the near field)."""
    n = 2048
    pts, hm, F = _system(n, "gaussian", rng, 4, seed_scale=16.0)
    kw = dict(tol=1e-4, max_iter=800)
    _, plain = make_solver(hm, 1e-2, precondition=False, **kw)(F)
    _, pc = make_solver(hm, 1e-2, precondition=True, **kw)(F)
    assert plain.converged and pc.converged
    assert pc.iterations < plain.iterations, (pc.iterations, plain.iterations)


def test_diagonal_blocks_match_dense(rng):
    """diagonal_blocks == the (i, i) leaf blocks of the tree-ordered dense
    matrix on the real rows; pad rows/cols are zeroed with a unit diagonal
    (decoupled identity rows, SPD for any shift)."""
    n = 600
    pts = halton(n, 2)
    hm = build_hmatrix(pts, "gaussian", k=8, c_leaf=128)
    blocks = diagonal_blocks(hm)
    a_tree = hm.kernel(hm.tree.points, hm.tree.points)
    c = hm.plan.c_leaf
    assert blocks.shape == (hm.plan.n_pad // c, c, c)
    valid = np.arange(hm.plan.n_pad) < n
    for i in [0, 1, blocks.shape[0] - 1]:
        want = np.asarray(a_tree[i * c:(i + 1) * c, i * c:(i + 1) * c]).copy()
        v = valid[i * c:(i + 1) * c]
        want[~v, :] = 0.0
        want[:, ~v] = 0.0
        want[~v, ~v] = 1.0
        np.testing.assert_allclose(np.asarray(blocks[i]), want,
                                   rtol=1e-6, atol=1e-6)


def test_diagonal_blocks_ragged_last_leaf_spd(rng):
    """Regression: a ragged last leaf (n < n_pad) used to keep kernel
    values in the pad rows/cols of the final diagonal block, making the
    shifted block ill-posed for Cholesky-based preconditioning.  Masked
    pad rows carry exactly a unit diagonal, so every block stays SPD and
    the block-Jacobi solve is unaffected on the real rows."""
    n = 600                                  # 600 = 4*128 + 88: ragged tail
    pts = halton(n, 2)
    hm = build_hmatrix(pts, "gaussian", k=8, c_leaf=128)
    blocks = np.asarray(diagonal_blocks(hm))
    last = blocks[-1]
    tail = n % hm.plan.c_leaf
    assert tail != 0                         # the case under test
    np.testing.assert_array_equal(last[tail:, :tail], 0.0)
    np.testing.assert_array_equal(last[:tail, tail:], 0.0)
    np.testing.assert_array_equal(last[tail:, tail:],
                                  np.eye(hm.plan.c_leaf - tail,
                                         dtype=last.dtype))
    for b in blocks:                         # SPD under the usual shift
        np.linalg.cholesky(b.astype(np.float64)
                           + 1e-2 * np.eye(b.shape[0]))


@pytest.mark.parametrize("b,c", [(1, 128), (3, 128), (2, 256)])
def test_block_cholesky_kernel_matches_ref(b, c, rng):
    q = rng.randn(b, c, c).astype(np.float32)
    a = jnp.asarray(q @ np.swapaxes(q, 1, 2) + c * np.eye(c, dtype=np.float32))
    l_kern = batched_block_cholesky(a)
    l_ref = batched_block_cholesky_ref(a)
    np.testing.assert_allclose(np.asarray(l_kern), np.asarray(l_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,c,r", [(1, 128, 1), (3, 128, 8), (2, 256, 4)])
def test_block_cholesky_solve_kernel_matches_ref(b, c, r, rng):
    q = rng.randn(b, c, c).astype(np.float32)
    a = jnp.asarray(q @ np.swapaxes(q, 1, 2) + c * np.eye(c, dtype=np.float32))
    l = batched_block_cholesky_ref(a)
    x = jnp.asarray(rng.randn(b, c, r).astype(np.float32))
    y_kern = batched_block_cholesky_solve(l, x)
    y_ref = batched_block_cholesky_solve_ref(l, x)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_solve_server_panels(rng):
    """HMatrixSolveServer == per-target make_solver across panel boundaries
    and padding; zero-padded columns must not change real results."""
    from repro.serve.step import HMatrixSolveServer
    n = 512
    pts, hm, F = _system(n, "gaussian", rng, 6)
    srv = HMatrixSolveServer(hm, SIGMA2, max_batch=4, tol=1e-6, max_iter=400)
    outs = srv.serve([F[:, j] for j in range(6)])
    assert len(outs) == 6 and len(srv.last_info) == 2
    solver = make_solver(hm, SIGMA2, tol=1e-6, max_iter=400)
    for j, cj in enumerate(outs):
        ref, _ = solver(F[:, j])
        # panel and single-column CG take different active-mask paths; both
        # converge below tol, so solutions agree to O(kappa * tol)
        np.testing.assert_allclose(np.asarray(cj), np.asarray(ref),
                                   rtol=1e-2, atol=1e-4)
    with pytest.raises(ValueError):
        srv.serve([np.zeros(n + 1, np.float32)])
