"""Adaptive cross approximation (paper §2.4, Algorithm 2) — fixed rank form.

The paper's practical implementation drops the Frobenius stopping criterion
and imposes a fixed maximum rank ``k`` (§2.4 last paragraph, §6.4): this makes
the batched version a *static* ``fori_loop`` — ideal for TPUs (DESIGN.md §3.4).
Row pivots come from the infinity-norm of the residual column (as in Alg. 2);
column pivots follow the standard partial-pivoting rule (argmax of the last
residual row), with used rows/columns masked out.

Matrix entries are generated on the fly from the kernel function and the
point coordinates — the paper's key memory trick (§5.4: "we normally always
re-compute ... during each application").

``aca_fixed_rank``  — single block, pure jnp (oracle for the Pallas kernel).
``batched_aca``     — vmap over a batch of equally-sized blocks (one block
                      cluster tree level), the paper's §5.4.1 batching.
``aca_adaptive``    — reference variant WITH the Frobenius stopping criterion
                      (used only by the convergence study / tests).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


def _masked_argmax(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """argmax of |x| over positions where mask (1.0 = available)."""
    return jnp.argmax(jnp.abs(x) * mask - (1.0 - mask))


@partial(jax.jit, static_argnames=("kernel", "k"))
def aca_fixed_rank(row_pts: jnp.ndarray, col_pts: jnp.ndarray,
                   kernel: Callable, k: int):
    """Rank-``k`` cross approximation of A[i,j] = kernel(row_pts[i], col_pts[j]).

    Returns (U, V) with A ~= U @ V.T, U: (m, k), V: (n, k).

    Degenerate pivots (residual exactly 0 — block has rank < r) yield zero
    columns, so UV^T is still exact in that case.
    """
    m, n = row_pts.shape[0], col_pts.shape[0]
    dtype = row_pts.dtype
    # carries built *like* the points: inside a shard_map whose block
    # lists are split over a mesh axis (hshard's row sharding) the points
    # vary over that axis, and the loop carry must vary with them.  The
    # factors are carried transposed, (k, m) and (k, n): with the block
    # axis minor a batch of them is lane-dense on the TPU, where (m, k)
    # pads k = 16 to 128 lanes.  On a v5e building N = 2^18 (c_leaf 256,
    # k 16) the (m, k) carry took 4.30 s for the factor stage and 14.8 GB
    # of temporaries for its largest level; this one takes 1.34 s and
    # 6.7 GB, with the same apply error.
    like = row_pts + col_pts[:1]
    Ut0 = jnp.zeros_like(like, shape=(k, m))
    Vt0 = jnp.zeros_like(like, shape=(k, n))
    row_mask0 = jnp.ones_like(like, shape=(m,))
    col_mask0 = jnp.ones_like(like, shape=(n,))
    j0 = jnp.zeros_like(like, jnp.int32, shape=())

    def body(r, carry):
        Ut, Vt, row_mask, col_mask, j_r = carry
        # residual column j_r:  A[:, j_r] - U @ V[j_r]   (a k-term sum per
        # entry, taken elementwise in float32 rather than as a matmul)
        a_col = kernel(row_pts, col_pts[j_r][None, :])[:, 0]
        u_hat = a_col - jnp.sum(Vt[:, j_r][:, None] * Ut, axis=0)
        i_r = _masked_argmax(u_hat, row_mask)
        alpha = u_hat[i_r]
        safe = jnp.abs(alpha) > jnp.asarray(1e-30, dtype)
        inv = jnp.where(safe, 1.0 / jnp.where(safe, alpha, 1.0), 0.0)
        u_r = u_hat * inv
        # residual row i_r:  A[i_r, :] - V @ U[i_r]
        a_row = kernel(row_pts[i_r][None, :], col_pts)[0, :]
        v_r = a_row - jnp.sum(Ut[:, i_r][:, None] * Vt, axis=0)
        v_r = jnp.where(safe, v_r, jnp.zeros_like(v_r))
        u_r = jnp.where(safe, u_r, jnp.zeros_like(u_r))
        Ut = Ut.at[r].set(u_r)
        Vt = Vt.at[r].set(v_r)
        row_mask = row_mask.at[i_r].set(0.0)
        col_mask = col_mask.at[j_r].set(0.0)
        j_next = _masked_argmax(v_r, col_mask).astype(jnp.int32)
        return Ut, Vt, row_mask, col_mask, j_next

    Ut, Vt, _, _, _ = jax.lax.fori_loop(
        0, k, body, (Ut0, Vt0, row_mask0, col_mask0, j0))
    return Ut.T, Vt.T


@partial(jax.jit, static_argnames=("kernel", "k"))
def batched_aca(row_pts: jnp.ndarray, col_pts: jnp.ndarray,
                kernel: Callable, k: int):
    """Batched fixed-rank ACA over B equally-sized blocks.

    row_pts: (B, m, d), col_pts: (B, n, d) -> U: (B, m, k), V: (B, n, k).
    """
    return jax.vmap(lambda rp, cp: aca_fixed_rank(rp, cp, kernel, k))(row_pts, col_pts)


def aca_adaptive(a: jnp.ndarray, eps: float, k_max: int, eta: float = 0.0):
    """Algorithm 2 verbatim (with stopping criterion) on an explicit matrix.

    Reference/benchmark only (host loop, not jitted).  Returns (U, V, rank).
    """
    import numpy as np

    a = np.asarray(a, np.float64)
    m, n = a.shape
    U = np.zeros((m, k_max))
    V = np.zeros((n, k_max))
    row_mask = np.ones(m, bool)
    col_mask = np.ones(n, bool)
    j_r = 0
    frob_sq = 0.0
    rank = k_max
    for r in range(k_max):
        u_hat = a[:, j_r] - U[:, :r] @ V[j_r, :r]
        cand = np.where(row_mask, np.abs(u_hat), -1.0)
        i_r = int(np.argmax(cand))
        alpha = u_hat[i_r]
        if abs(alpha) < 1e-300:
            rank = r
            break
        u_r = u_hat / alpha
        v_r = a[i_r, :] - V[:, :r] @ U[i_r, :r]
        U[:, r] = u_r
        V[:, r] = v_r
        row_mask[i_r] = False
        col_mask[j_r] = False
        # ||sum_l u_l v_l||_F^2 update (paper's criterion RHS)
        frob_sq += (u_r @ u_r) * (v_r @ v_r)
        for l in range(r):
            frob_sq += 2.0 * (U[:, l] @ u_r) * (V[:, l] @ v_r)
        nu, nv = np.linalg.norm(u_r), np.linalg.norm(v_r)
        if nu * nv <= eps * (1.0 - eta) / (1.0 + eps) * np.sqrt(max(frob_sq, 0.0)):
            rank = r + 1
            break
        if not (row_mask.any() and col_mask.any()):
            # every row or column pivot is consumed: the cross approximation
            # is complete.  Keeping the stale j_r here would re-cross an
            # already-consumed column whose residual is float-noise (far
            # above the 1e-300 alpha guard), normalizing garbage into the
            # next rank-1 term — clamp the rank and stop instead.
            rank = r + 1
            break
        j_r = int(np.argmax(np.where(col_mask, np.abs(v_r), -1.0)))
    return U[:, :rank], V[:, :rank], rank
