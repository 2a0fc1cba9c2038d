"""jit'd public wrappers for the batched block Cholesky Pallas kernels.

Same dispatch discipline as the other kernel packages: blocks whose VMEM
working set would overflow the budget fall back to the jnp oracle path;
``interpret`` is auto-detected per backend inside the kernels (compiled on
TPU, interpreter elsewhere).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT, force_ref, vmem_bytes

from .kernel import batched_block_cholesky_solve_t, batched_block_cholesky_t
from .ref import batched_block_cholesky_ref, batched_block_cholesky_solve_ref

VMEM_BUDGET = VMEM_LIMIT


def _chol_vmem_bytes(c: int) -> int:
    return vmem_bytes([(c, c), (c, c)], [(c, c)] * 3)


def _solve_vmem_bytes(c: int, r: int) -> int:
    return vmem_bytes([(c, c), (r, c), (r, c)], [(r, c)] * 3)


def batched_block_cholesky(a: jnp.ndarray) -> jnp.ndarray:
    """Batched in-VMEM Cholesky ``L[b] = cholesky(A[b])``.

    Parameters
    ----------
    a : jnp.ndarray, shape (B, c, c)
        SPD blocks (the shifted inadmissible diagonal leaf blocks
        ``A_ii + sigma^2 I`` of the block-Jacobi preconditioner).

    Returns
    -------
    l : jnp.ndarray, shape (B, c, c)
        Lower Cholesky factors (right-looking factorization, one block per
        program).  Oversized blocks fall back to the jnp oracle.
    """
    c = a.shape[1]
    if force_ref() or _chol_vmem_bytes(c) > VMEM_BUDGET:
        return batched_block_cholesky_ref(a)
    return batched_block_cholesky_t(a)


def batched_block_cholesky_solve(l: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Per-iteration block-Jacobi apply ``Y[b] = (L[b] L[b]^T)^{-1} X[b]``.

    Parameters
    ----------
    l : jnp.ndarray, shape (B, c, c)
        Lower factors from :func:`batched_block_cholesky`.
    x : jnp.ndarray, shape (B, c, R)
        Residual panel reshaped to leaf blocks (contiguous in tree order).

    Returns
    -------
    y : jnp.ndarray, shape (B, c, R)
        Forward + back substitution per block, all R columns at once.
    """
    c = l.shape[1]
    r = x.shape[2]
    if force_ref() or _solve_vmem_bytes(c, r) > VMEM_BUDGET:
        return batched_block_cholesky_solve_ref(l, x)
    return batched_block_cholesky_solve_t(l, x)
