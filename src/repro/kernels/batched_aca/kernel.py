"""Pallas TPU kernel: batched fixed-rank adaptive cross approximation.

The paper's §5.4.1 batched ACA — the single most important batching win in
the paper (32x on GPU, Fig 15).  TPU adaptation (DESIGN.md §3.3/3.4):

  * fixed rank k  ->  static ``fori_loop`` (no voting mechanism needed: every
    block runs exactly k pivoted rank-1 updates);
  * matrix entries generated on the fly from the point coordinates — only one
    column + one row of the block ever exist per iteration (O(m+n) VMEM);
  * data-dependent pivoting stays *inside* the kernel: ``argmax`` over the
    masked residual picks the row pivot, the masked last residual row picks
    the next column pivot (partial pivoting, as in Algorithm 2).

Grid: one program per block b.
VMEM working set per program (m = n = block size, f32):
    rows_t/cols_t : 2 * d * m * 4 B
    U^T, V^T      : 2 * m * k * 4 B     (loop carry)
    masks, rows   : ~4 * m * 4 B
  m=8192, k=32, d=3: ~2.4 MB << 16 MB VMEM.  The ops wrapper falls back to
  the jnp path for coarser levels whose blocks exceed the VMEM budget — the
  TPU analogue of the paper's ``bs_ACA`` batching-size heuristic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import default_interpret
from .._phi import pairwise_sqdist_t, phi_from_sqdist


def _masked_argmax(x, mask, lanes):
    """First lane maximising ``|x|`` where ``mask`` (1.0 = available).

    Same tie rule as ``jnp.argmax`` (first occurrence), built from two lane
    reductions so it lowers on the TPU: returns a (1, 1) float lane index
    (exact below 2^24).
    """
    val = jnp.abs(x) * mask - (1.0 - mask)
    best = jnp.max(val, axis=1, keepdims=True)
    return jnp.min(jnp.where(val == best, lanes, float(x.shape[1])),
                   axis=1, keepdims=True)


def _lane(x, lanes, idx):
    """``x[:, idx]`` as a (rows, 1) column: a masked lane reduction."""
    return jnp.sum(jnp.where(lanes == idx, x, 0.0), axis=1, keepdims=True)


def _phi_to(pts_t, p, kernel_name: str, point_dim: int):
    """phi between one point ``p`` (d, 1) and ``pts_t`` (d, n) -> (1, n)."""
    diff = pts_t - p
    d2 = jnp.sum(diff * diff, axis=0, keepdims=True)
    return phi_from_sqdist(d2, kernel_name, point_dim, accuracy=None)


def _kernel(rows_t_ref, cols_t_ref, ut_ref, vt_ref, *, k: int,
            kernel_name: str, point_dim: int):
    """Rank-k ACA of one block, factors kept transposed (k, m) / (k, n).

    Every vector is 2-D with the block axis on the lanes; pivots are
    (1, 1) float indices and every gather is a masked lane reduction, so
    no dynamic slice of a value is needed.
    """
    rows_t = rows_t_ref[0]          # (d, m)
    cols_t = cols_t_ref[0]          # (d, n)
    m = rows_t.shape[1]
    n = cols_t.shape[1]
    dtype = rows_t.dtype
    lanes_m = lax.broadcasted_iota(jnp.int32, (1, m), 1).astype(dtype)
    lanes_n = lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(dtype)
    ranks = lax.broadcasted_iota(jnp.int32, (k, 1), 0)

    def body(r, carry):
        ut, vt, row_mask, col_mask, j_r = carry
        # residual column j_r:  A[:, j_r] - U @ V[j_r]          (1, m)
        a_col = _phi_to(rows_t, _lane(cols_t, lanes_n, j_r), kernel_name,
                        point_dim)
        u_hat = a_col - jnp.sum(ut * _lane(vt, lanes_n, j_r), axis=0,
                                keepdims=True)
        i_r = _masked_argmax(u_hat, row_mask, lanes_m)
        alpha = _lane(u_hat, lanes_m, i_r)                     # (1, 1)
        safe = jnp.abs(alpha) > jnp.asarray(1e-30, dtype)
        inv = jnp.where(safe, 1.0 / jnp.where(safe, alpha, 1.0), 0.0)
        u_r = u_hat * inv
        # residual row i_r:  A[i_r, :] - V @ U[i_r]             (1, n)
        a_row = _phi_to(cols_t, _lane(rows_t, lanes_m, i_r), kernel_name,
                        point_dim)
        v_r = a_row - jnp.sum(vt * _lane(ut, lanes_m, i_r), axis=0,
                              keepdims=True)
        v_r = jnp.where(safe, v_r, 0.0)
        onehot_r = (ranks == r).astype(dtype)                  # (k, 1)
        ut = ut + onehot_r * u_r
        vt = vt + onehot_r * v_r
        row_mask = row_mask * (1.0 - (lanes_m == i_r).astype(dtype))
        col_mask = col_mask * (1.0 - (lanes_n == j_r).astype(dtype))
        j_next = _masked_argmax(v_r, col_mask, lanes_n)
        return ut, vt, row_mask, col_mask, j_next

    init = (jnp.zeros((k, m), dtype), jnp.zeros((k, n), dtype),
            jnp.ones((1, m), dtype), jnp.ones((1, n), dtype),
            jnp.zeros((1, 1), dtype))
    ut, vt, _, _, _ = lax.fori_loop(0, k, body, init)
    ut_ref[0] = ut
    vt_ref[0] = vt


@functools.partial(jax.jit, static_argnames=("kernel_name", "k", "interpret"))
def batched_aca_t(rows_t: jnp.ndarray, cols_t: jnp.ndarray,
                  kernel_name: str, k: int, interpret: bool | None = None):
    """Batched rank-k ACA.  rows_t: (B, d, m), cols_t: (B, d, n).

    Returns (U, V): (B, m, k), (B, n, k) with phi(rows, cols) ~= U V^T.
    The kernel writes the factors lane-major, (B, k, m) and (B, k, n); the
    transposes back are fused into the surrounding program by XLA.
    """
    if interpret is None:
        interpret = default_interpret()
    b, d, m = rows_t.shape
    n = cols_t.shape[2]
    ut, vt = pl.pallas_call(
        functools.partial(_kernel, k=k, kernel_name=kernel_name, point_dim=d),
        name="batched_aca",
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, d, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, d, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k, m), rows_t.dtype),
            jax.ShapeDtypeStruct((b, k, n), rows_t.dtype),
        ],
        interpret=interpret,
    )(rows_t, cols_t)
    return jnp.swapaxes(ut, 1, 2), jnp.swapaxes(vt, 1, 2)


# ---------------------------------------------------------------------------
# Batched low-rank APPLY, multi-RHS: Y[b] = U[b] @ (V[b]^T @ X[b]).
# The §5.4.1 application step in matmat form — two MXU contractions
# (k x m) @ (m, R) and (m, k) @ (k, R) per block, no kernel regeneration.
# VMEM per program (m = n = block size, f32):
#     U, V      : 2 * m * k * 4 B
#     X, T, Y   : (2 * m * R + k * R) * 4 B
#   m=4096, k=32, R=64: ~3.2 MB << 16 MB VMEM.
# ---------------------------------------------------------------------------


def _lowrank_mm_kernel(u_ref, v_ref, x_ref, y_ref):
    u = u_ref[0]                      # (m, k)
    v = v_ref[0]                      # (n, k)
    x = x_ref[0]                      # (n, R)
    t = jnp.dot(v.T, x, preferred_element_type=jnp.float32)   # (k, R)  MXU
    y_ref[0] = jnp.dot(u, t, preferred_element_type=jnp.float32)  # (m, R) MXU


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_lowrank_matmat_t(u: jnp.ndarray, v: jnp.ndarray, x: jnp.ndarray,
                             interpret: bool | None = None) -> jnp.ndarray:
    """u: (B, m, k), v: (B, n, k), x: (B, n, R) -> (B, m, R).

    (Factors are already in the kernel's preferred layout — the ``_t``
    suffix just follows the package convention of kernel-level entry
    points; the public dispatch lives in ops.py.)
    """
    if interpret is None:
        interpret = default_interpret()
    b, m, k = u.shape
    n = v.shape[1]
    r = x.shape[2]
    return pl.pallas_call(
        _lowrank_mm_kernel,
        name="batched_lowrank_matmat",
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, m, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, r), x.dtype),
        interpret=interpret,
    )(u, v, x)
