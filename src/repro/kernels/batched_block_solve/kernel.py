"""Pallas TPU kernels: batched Cholesky factorize/solve of diagonal blocks.

Block-Jacobi preconditioning for the fused H-matrix Krylov solve
(``repro.solve``): the ``(B, c, c)`` inadmissible diagonal leaf blocks
``A_ii + sigma^2 I`` are factorized ONCE at solver setup and their
triangular solves applied every CG iteration.  Both stages run entirely in
VMEM, one program per block:

  * ``batched_block_cholesky_t`` — right-looking Cholesky as ``c`` pivoted
    rank-1 updates (``fori_loop``; pivot column and row extracted by masked
    reductions, the trailing submatrix update is a VPU outer-product
    subtraction);
  * ``batched_block_cholesky_solve_t`` — forward + back substitution on a
    transposed ``(R, c)`` panel (``L L^T Y = X``), ``2c`` steps of O(c R)
    each that read one row of ``L`` apiece.

VMEM working set per program (c = C_leaf, f32):
    factorize: A + L                 2 * c * c * 4 B
    solve:     L + X, Y panels       (c^2 + 3 c R) * 4 B
  c=512, R=64: ~2.3 MB << 16 MB VMEM.  ``ops.py`` falls back to the jnp
  oracle for blocks over the VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import default_interpret

_TINY = 1e-30  # pivot clamp: blocks are SPD by construction (sigma^2 shift)


def _chol_kernel(a_ref, l_ref):
    a = a_ref[0]                                   # (c, c), symmetric PD
    c = a.shape[0]
    dtype = a.dtype
    idx_col = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    idx_row = lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def body(j, carry):
        l_mat, a_r = carry
        # row / column / pivot j of the residual by masked reductions (the
        # TPU kernel compiler lowers no dynamic slice of a value)
        row = jnp.sum(jnp.where(idx_col == j, a_r, 0.0), axis=0,
                      keepdims=True)                           # A_r[j, :]
        col = jnp.sum(jnp.where(idx_row == j, a_r, 0.0), axis=1,
                      keepdims=True)                           # A_r[:, j]
        d2 = jnp.sum(jnp.where(idx_row == j, row, 0.0), axis=1,
                     keepdims=True)                            # A_r[j, j]
        dinv = lax.rsqrt(jnp.maximum(d2, jnp.asarray(_TINY, dtype)))
        l_col = jnp.where(idx_col >= j, col * dinv, 0.0)       # (c, 1)
        l_row = jnp.where(idx_row >= j, row * dinv, 0.0)       # (1, c)
        e_row = (idx_row == j).astype(dtype)
        l_mat = l_mat + l_col * e_row                          # write column j
        a_r = a_r - l_col * l_row                              # rank-1 update
        return l_mat, a_r

    l_mat, _ = lax.fori_loop(0, c, body, (jnp.zeros_like(a), a))
    l_ref[0] = l_mat


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_block_cholesky_t(a: jnp.ndarray,
                             interpret: bool | None = None) -> jnp.ndarray:
    """L[b] = cholesky(A[b]) (lower).  a: (B, c, c) SPD -> (B, c, c)."""
    if interpret is None:
        interpret = default_interpret()
    b, c, _ = a.shape
    return pl.pallas_call(
        _chol_kernel,
        name="batched_block_cholesky",
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, c), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, c, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, c), a.dtype),
        interpret=interpret,
    )(a)


def _chol_solve_kernel(l_ref, xt_ref, zt_ref):
    xt = xt_ref[0]                                 # (R, c): panel, transposed
    c = xt.shape[1]
    lanes = lax.broadcasted_iota(jnp.int32, (1, c), 1)

    def l_row(j):
        return l_ref[0, pl.ds(j, 1), :]            # (1, c): row j of L

    def fwd(j, yt):
        # row form  y_j = (x_j - L[j, :j] y[:j]) / L[j, j]
        row = l_row(j)
        hot = lanes == j
        d = jnp.sum(jnp.where(hot, row, 0.0), axis=1, keepdims=True)
        xj = jnp.sum(jnp.where(hot, xt, 0.0), axis=1, keepdims=True)
        s = jnp.sum(row * yt, axis=1, keepdims=True)   # y is 0 from lane j on
        return yt + jnp.where(hot, (xj - s) / d, 0.0)

    def bwd(t, carry):
        # column form of L^T z = y:  z_i = y_i / L[i, i], then
        # y[:i] -= L[i, :i]^T z_i  (column i of L^T is row i of L)
        zt, yt = carry
        i = c - 1 - t
        row = l_row(i)
        hot = lanes == i
        d = jnp.sum(jnp.where(hot, row, 0.0), axis=1, keepdims=True)
        zi = jnp.sum(jnp.where(hot, yt, 0.0), axis=1, keepdims=True) / d
        zt = zt + jnp.where(hot, zi, 0.0)
        yt = yt - zi * jnp.where(lanes < i, row, 0.0)
        return zt, yt

    yt = lax.fori_loop(0, c, fwd, jnp.zeros_like(xt))          # L Y1 = X
    zt, _ = lax.fori_loop(0, c, bwd, (jnp.zeros_like(xt), yt))  # L^T Y = Y1
    zt_ref[0] = zt


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_block_cholesky_solve_t(l: jnp.ndarray, x: jnp.ndarray,
                                   interpret: bool | None = None) -> jnp.ndarray:
    """Y[b] = (L[b] L[b]^T)^{-1} X[b].  l: (B, c, c), x: (B, c, R).

    The kernel works on the transposed panel (B, R, c) so that every
    substitution step reads one ROW of ``L`` (a dynamic sublane load) and
    touches O(c R) values; the transposes are fused by XLA.
    """
    if interpret is None:
        interpret = default_interpret()
    b, c, _ = l.shape
    r = x.shape[2]
    zt = pl.pallas_call(
        _chol_solve_kernel,
        name="batched_block_cholesky_solve",
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, c, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, c), x.dtype),
        interpret=interpret,
    )(l, jnp.swapaxes(x, 1, 2))
    return jnp.swapaxes(zt, 1, 2)
