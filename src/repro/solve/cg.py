"""Fused batched H-matrix solve: multi-RHS PCG as one jitted ``while_loop``.

``make_solver(hm, sigma2)`` compiles the ENTIRE regularized solve
``(A + sigma^2 I) C = F`` — F an ``(N, R)`` panel of right-hand sides —
into a single device program.  Design notes:

Active-mask convergence, no host sync.  The pre-fusion CG
(:func:`host_loop_cg`) is a host Python loop: every iteration fetches
``float(||r||)`` back to the host to decide termination, which serializes a
device->host round trip plus a fresh dispatch cascade per step — exactly
the per-product overhead the paper's batching patterns exist to amortize.
Here termination is data: each of the R columns carries its own
``alpha/beta`` (R independent CG runs in lockstep, one fused matmat per
iteration) and an *active* flag.  A column whose residual drops below
``tol`` freezes in place — its ``alpha``/``beta`` are masked to zero so
``x/r/p`` stop moving (no drift, no extra matmat effect, and no NaNs from
the vanishing ``r^T z``/``p^T A p`` quotients) — and the ``while_loop``
exits when every column is frozen or ``max_iter`` is hit.  The device
decides everything; the host blocks exactly once, when results are read.

Inlined operator.  The loop body calls
:func:`repro.core.hmatrix.apply_in_tree_order` — the same ACA level batches
and on-the-fly dense leaf batches as ``make_apply`` — directly on
tree-ordered panels.  The Morton permutation in/out is paid once per solve
instead of twice per iteration, and XLA fuses the vector updates between
matmats instead of dispatching them one by one.

Block-Jacobi preconditioning.  The inadmissible diagonal leaf blocks
(:func:`repro.core.hmatrix.diagonal_blocks`) shifted by ``sigma^2 I`` are
Cholesky-factorized once at setup (``kernels/batched_block_solve``); every
iteration then applies ``z = M^{-1} r`` as B independent ``(c, c)``
triangular solves on the reshaped panel — a contiguous reshape, because CG
runs in tree ordering where leaf clusters are contiguous index ranges.  The
near-field interactions these blocks capture dominate the conditioning of
the Gaussian-kernel systems, cutting iteration counts.

Padded tail.  ``n_pad > n`` rows (duplicated points) are masked out of the
operator and the preconditioner output, so the iteration runs exactly on
the leading ``(n, n)`` principal submatrix system; the pad stays zero in
``x/r/p`` by induction.

Multi-device.  The traceable loop body is factored out as
:func:`pcg_tree_ordered` with a pluggable ``reduce_any`` hook on the
"any column still active" predicate.  ``repro.parallel.hshard`` wraps it in
a ``shard_map`` over a device mesh (RHS columns sharded across devices,
the predicate ``psum``-reduced so every device runs the same trip count);
``make_solver(..., mesh=...)`` is the front door to that path.
"""
from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import permute_from_tree, permute_to_tree
from repro.core.hmatrix import HMatrix, apply_in_tree_order, diagonal_blocks
from repro.harith.hlu import HLUFactors, hlu_solve_panels
from repro.harith.precond import HLUPreconditioner, make_hlu_preconditioner


class SolveInfo:
    """LAZY convergence record of one fused solve.

    Construction stores the solver's DEVICE arrays as-is — no ``int()`` /
    ``np.asarray()`` — so building a ``SolveInfo`` never blocks on the
    device.  This is what lets panel launches overlap: the serving runtime
    can launch solve k+1 while solve k still computes, because recording
    solve k's metadata no longer forces a device->host sync inside the
    launch.  The attributes below materialize (and cache) the host values
    on first access; :meth:`fetch` forces all of them explicitly.

    Attributes
    ----------
    iterations : int
        while_loop trips until all columns froze.
    iters_per_column : np.ndarray, shape (R,)
        Trips until each column froze.
    residual_norms : np.ndarray, shape (R,)
        Final ``||b - (A + sigma^2 I) x||_2`` per column.
    converged : bool
        All columns below ``tol`` within ``max_iter``.
    """

    __slots__ = ("_it", "_iters_col", "_res", "_tol", "_host", "_lock")

    def __init__(self, iterations, iters_per_column, residual_norms,
                 tol: float):
        self._it = iterations
        self._iters_col = iters_per_column
        self._res = residual_norms
        self._tol = float(tol)
        self._host = None
        # the async serve path shares records across the scheduler thread
        # and any number of awaiting clients: first-fetch must be atomic
        self._lock = threading.Lock()

    def fetch(self) -> "SolveInfo":
        """Materialize every field on host (ONE blocking read) and return self."""
        with self._lock:
            if self._host is None:
                self._host = (int(self._it), np.asarray(self._iters_col),
                              np.asarray(self._res))
                self._it = self._iters_col = self._res = None  # drop dev refs
        return self

    @property
    def iterations(self) -> int:
        return self.fetch()._host[0]

    @property
    def iters_per_column(self) -> np.ndarray:
        return self.fetch()._host[1]

    @property
    def residual_norms(self) -> np.ndarray:
        return self.fetch()._host[2]

    @property
    def converged(self) -> bool:
        return bool(np.all(self.residual_norms < self._tol))

    def __repr__(self) -> str:                     # never forces the sync
        if self._host is None:
            return "SolveInfo(<pending on device>)"
        return (f"SolveInfo(iterations={self._host[0]}, "
                f"converged={self.converged})")


def host_loop_cg(matmat: Callable, b: jnp.ndarray, tol: float = 1e-5,
                 max_iter: int = 300):
    """Pre-fusion multi-RHS CG (benchmark baseline): host Python loop with a
    device->host residual sync per iteration.  b: (N, R) -> (x, iterations)."""
    x = jnp.zeros_like(b)
    r = b - matmat(x)
    p, rs = r, jnp.sum(r * r, axis=0)                        # (R,)
    for it in range(max_iter):
        ap = matmat(p)
        den = jnp.sum(p * ap, axis=0)
        alpha = jnp.where(den > 0, rs / jnp.where(den > 0, den, 1.0), 0.0)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * ap
        rs_new = jnp.sum(r * r, axis=0)
        if float(jnp.sqrt(rs_new.max())) < tol:              # ALL columns done
            return x, it + 1
        beta = jnp.where(rs > 0, rs_new / jnp.where(rs > 0, rs, 1.0), 0.0)
        p = r + beta[None, :] * p
        rs = rs_new
    return x, max_iter


def build_preconditioner(hm: HMatrix, sigma2: float,
                         use_pallas: bool = False) -> jnp.ndarray:
    """Cholesky-factorize the block-Jacobi preconditioner once at setup.

    Parameters
    ----------
    hm : HMatrix
        Assembled H-matrix; supplies the inadmissible diagonal leaf blocks.
    sigma2 : float
        Regularization shift added to each diagonal block before
        factorization (also makes the padded-tail blocks SPD).
    use_pallas : bool, optional
        Route the factorization through the ``batched_block_solve`` Pallas
        kernel instead of the jnp oracle.

    Returns
    -------
    chol : jnp.ndarray, shape (n_leaf, c, c)
        Lower Cholesky factors of ``A_ii + sigma2 I`` per leaf cluster, in
        tree order — ready for :func:`pcg_tree_ordered`'s per-iteration
        ``z = M^{-1} r`` triangular solves.
    """
    c = hm.plan.c_leaf
    blocks = diagonal_blocks(hm) + sigma2 * jnp.eye(c, dtype=hm.tree.points.dtype)
    if use_pallas:
        from repro.kernels.batched_block_solve.ops import batched_block_cholesky
        return batched_block_cholesky(blocks)
    from repro.kernels.batched_block_solve.ref import batched_block_cholesky_ref
    return batched_block_cholesky_ref(blocks)


def pcg_tree_ordered(tree, plan, kernel, k: int, use_pallas: bool,
                     sigma2: float, tol2: float, max_iter: int,
                     points: jnp.ndarray, factors, chol_arg,
                     b_pad: jnp.ndarray, reduce_any: Callable = jnp.any):
    """Traceable active-mask PCG ``while_loop`` on a TREE-ordered panel.

    This is the shared loop body of the single-device solver
    (:func:`make_solver`) and the mesh-sharded solver
    (``repro.parallel.hshard.make_sharded_solver``): no permutations, no
    jit — callers wrap it.

    Parameters
    ----------
    tree, plan, kernel, k : ClusterTree, HMatrixPlan, Callable, int
        The H-matrix structure (static; closed over by the caller's jit).
    use_pallas : bool
        Route the hot loops through the Pallas kernels.
    sigma2, tol2, max_iter : float, float, int
        Regularization shift, SQUARED absolute residual tolerance, and the
        iteration cap.
    points : jnp.ndarray, shape (n_pad, d)
        Tree-ordered coordinates, passed as a runtime argument (NOT a traced
        constant — see :func:`repro.core.hmatrix.make_apply`).
    factors : FactorStore | dict | None
        Stored ACA factors (P mode) — a
        :class:`repro.core.factor_store.FactorStore` or a legacy
        ``level -> (U, V)`` dict — or None (NP mode).  Flows through
        the ``while_loop`` body untouched as a pytree of packed level
        groups.
    chol_arg : jnp.ndarray | None
        Block-Jacobi factors from :func:`build_preconditioner`, or None for
        plain CG.
    b_pad : jnp.ndarray, shape (n_pad, R)
        Tree-ordered right-hand-side panel with a zeroed padded tail.
    reduce_any : Callable, optional
        Reduction mapping the ``(R,)`` active mask to the loop predicate.
        ``jnp.any`` on one device; the sharded path passes a ``psum``-based
        all-reduce so every device agrees on the trip count.

    Returns
    -------
    x_pad : jnp.ndarray, shape (n_pad, R)
        Solution panel in tree ordering (padded tail zero).
    it : jnp.ndarray, int32 scalar
        while_loop trips until all columns froze.
    iters_col : jnp.ndarray, int32, shape (R,)
        Trips until each column froze.
    rr : jnp.ndarray, shape (R,)
        Final squared residual norms ``||r_j||_2^2``.
    """
    n, n_pad = tree.n, tree.n_pad
    c = plan.c_leaf
    n_leaf = n_pad // c
    r_width = b_pad.shape[1]

    def _mask(v):
        if n_pad == n:
            return v
        pad_rows = jnp.arange(n_pad)[:, None] < n
        return jnp.where(pad_rows, v, 0.0)

    def apply_op(v):
        # The operator's matmuls run at HIGHEST precision.  At the TPU's
        # default (one bfloat16 pass) the apply's relative error is ~3e-3
        # of ||A||, which exceeds a small shift sigma2: on a v5e, N = 2^18
        # Halton points on a side-128 square with sigma2 = 1e-2 stopped at
        # 300 iterations with a relative residual of 1.1e4, where float32
        # takes 168 iterations (CPU, N = 2^16, side 64).
        with jax.default_matmul_precision("highest"):
            z = apply_in_tree_order(tree, plan, kernel, k, use_pallas,
                                    points, factors, v)
        return _mask(z + sigma2 * v)

    def prec(r):
        if chol_arg is None:
            return r
        if isinstance(chol_arg, HLUFactors):
            # approximate H-Cholesky: two block-substitution sweeps over
            # the factor tiles, inlined in the while_loop like the
            # block-Jacobi solves below (repro.harith.hlu)
            return _mask(hlu_solve_panels(chol_arg, r))
        rb = r.reshape(n_leaf, c, r_width)
        if use_pallas:
            from repro.kernels.batched_block_solve.ops import (
                batched_block_cholesky_solve)
            y = batched_block_cholesky_solve(chol_arg, rb)
        else:
            from repro.kernels.batched_block_solve.ref import (
                batched_block_cholesky_solve_ref)
            y = batched_block_cholesky_solve_ref(chol_arg, rb)
        return _mask(y.reshape(n_pad, r_width))

    r0 = b_pad                                           # x0 = 0
    z0 = prec(r0)
    rr0 = jnp.sum(r0 * r0, axis=0)                       # (R,) ||r||^2
    rs0 = jnp.sum(r0 * z0, axis=0)                       # (R,) r^T z
    active0 = rr0 > tol2
    state0 = (jnp.zeros_like(b_pad), r0, z0, rs0, rr0, active0,
              jnp.asarray(0, jnp.int32), jnp.zeros_like(rr0, jnp.int32))

    def cond(state):
        _, _, _, _, _, active, it, _ = state
        return jnp.logical_and(reduce_any(active), it < max_iter)

    def body(state):
        x, r, p, rs, rr, active, it, iters_col = state
        ap = apply_op(p)
        den = jnp.sum(p * ap, axis=0)
        ok = active & (den > 0)
        alpha = jnp.where(ok, rs / jnp.where(ok, den, 1.0), 0.0)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * ap
        rr_new = jnp.where(active, jnp.sum(r * r, axis=0), rr)
        z = prec(r)
        rs_new = jnp.sum(r * z, axis=0)
        still = active & (rr_new > tol2)
        beta = jnp.where(still, rs_new / jnp.where(active, rs, 1.0), 0.0)
        p = jnp.where(still[None, :], z + beta[None, :] * p, p)
        rs = jnp.where(still, rs_new, rs)
        iters_col = jnp.where(active, it + 1, iters_col)
        return x, r, p, rs, rr_new, still, it + 1, iters_col

    x, r, _, _, rr, _, it, iters_col = jax.lax.while_loop(cond, body, state0)
    return x, it, iters_col, rr


def make_solver(hm: HMatrix, sigma2: float, tol: float = 1e-5,
                max_iter: int = 300, precondition: bool = True,
                use_pallas: bool = False, mesh=None, axis=None,
                precond: str | HLUPreconditioner | None = None,
                hlu_opts: dict | None = None) -> Callable:
    """Build the fused solver for ``(A + sigma2 I) C = F``.

    Parameters
    ----------
    hm : HMatrix
        Assembled H-matrix (``build_hmatrix``), defining ``A``.
    sigma2 : float
        Regularization shift (ridge parameter).
    tol : float, optional
        Per-column ABSOLUTE residual tolerance: column ``j`` freezes once
        ``||r_j||_2 < tol``.
    max_iter : int, optional
        Iteration cap for the ``while_loop``.
    precondition : bool, optional
        Legacy on/off switch for block-Jacobi preconditioning; ignored
        when ``precond`` is given.
    use_pallas : bool, optional
        Route the hot loops (H-apply + block solves) through the Pallas
        kernels.
    mesh : jax.sharding.Mesh, optional
        When given, return the MULTI-DEVICE solver instead: the RHS panel is
        sharded column-wise over the mesh via ``shard_map`` and the PCG
        predicate is all-reduced so devices stay in lockstep (see
        ``repro.parallel.hshard.make_sharded_solver``).
    axis : str | tuple, optional
        Mesh axis (or axes) to shard over; default all axes of ``mesh``.
        Ignored without ``mesh``.
    precond : {"bj", "hlu", "none"} | HLUPreconditioner, optional
        Preconditioner selection.  ``"bj"`` is the block-Jacobi default;
        ``"hlu"`` factorizes an approximate H-Cholesky once at setup
        (``repro.harith``) and inlines its forward/back H-solve in the
        fused while_loop — near-constant iteration counts on
        ill-conditioned systems.  A prebuilt
        :class:`repro.harith.precond.HLUPreconditioner` is used as-is
        (this is how serving shares ONE factorization across the main
        and fallback solvers).  The chosen preconditioner is exposed as
        ``solve.preconditioner``.
    hlu_opts : dict, optional
        Keyword arguments for
        :func:`repro.harith.precond.make_hlu_preconditioner` (``tol``,
        ``kp``) when ``precond="hlu"`` builds the factorization here.

    Returns
    -------
    solve : Callable
        ``solve(F) -> (C, SolveInfo)``.  ``F`` may be a single target
        ``(N,)`` or a panel ``(N, R)``; ``C`` has the same shape.  One
        compiled program per distinct R: permute in, run the active-mask
        PCG ``while_loop`` to completion on device, permute out.  Both
        ``C`` and the :class:`SolveInfo` hold DEVICE arrays — nothing
        syncs until they are read (``np.asarray(C)`` / an info attribute /
        ``info.fetch()``), so launches can overlap.
    """
    pre = None
    if isinstance(precond, HLUPreconditioner):
        pre, precond = precond, "hlu"
    elif precond is None:
        precond = "bj" if precondition else "none"
    if precond not in ("bj", "hlu", "none"):
        raise ValueError(f"unknown precond {precond!r}; expected 'bj', "
                         "'hlu', 'none', or an HLUPreconditioner")
    if mesh is not None:
        if precond == "hlu":
            raise ValueError(
                "precond='hlu' is single-device: the H-LU substitution "
                "sweeps are sequential across block rows, which defeats "
                "the mesh-sharded solver's column parallelism — shard "
                "RHS columns over tenants instead, or use precond='bj'")
        from repro.parallel.hshard import make_sharded_solver
        return make_sharded_solver(hm, sigma2, mesh, axis=axis, tol=tol,
                                   max_iter=max_iter,
                                   precondition=precond == "bj",
                                   use_pallas=use_pallas)

    tree, plan, kernel, k = hm.tree, hm.plan, hm.kernel, hm.k
    n = tree.n
    tol2 = float(tol) * float(tol)
    if precond == "hlu":
        if pre is None:
            pre = make_hlu_preconditioner(hm, sigma2, use_pallas=use_pallas,
                                          **(hlu_opts or {}))
        chol = pre.factors
    elif precond == "bj":
        chol = build_preconditioner(hm, sigma2, use_pallas)
    else:
        chol = None

    @jax.jit
    def _solve(points, factors, chol_arg, b):
        b_pad = permute_to_tree(tree, b)                     # (n_pad, R), 0 tail
        x, it, iters_col, rr = pcg_tree_ordered(
            tree, plan, kernel, k, use_pallas, sigma2, tol2, max_iter,
            points, factors, chol_arg, b_pad)
        return permute_from_tree(tree, x), it, iters_col, jnp.sqrt(rr)

    def solve(f: jnp.ndarray):
        if f.ndim not in (1, 2) or f.shape[0] != n:
            raise ValueError(f"rhs shape {f.shape} incompatible with "
                             f"H-matrix of size ({n}, {n})")
        fp = f[:, None] if f.ndim == 1 else f
        x, it, iters_col, res = _solve(tree.points, hm.factors, chol, fp)
        # device arrays go straight into the lazy SolveInfo: no host sync
        # here, so back-to-back solve launches overlap (async dispatch)
        info = SolveInfo(it, iters_col, res, tol)
        return (x[:, 0] if f.ndim == 1 else x), info

    solve.preconditioner = pre
    return solve
