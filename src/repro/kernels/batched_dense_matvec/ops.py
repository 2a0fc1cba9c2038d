"""jit'd public wrappers for the batched dense kernel-matvec/matmat Pallas
kernels.

Both entry points transpose the (B, C, d) point arrays to the lane-major
(B, d, C) layout the kernels want (fused into the surrounding program by
XLA) and dispatch; ``interpret`` is auto-detected per backend inside the
kernels (compiled on TPU, interpreter elsewhere).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT, force_ref, vmem_bytes

from .kernel import batched_kernel_matmat_t
from .ref import batched_kernel_matmat_ref, batched_kernel_matvec_ref

VMEM_BUDGET = VMEM_LIMIT


def _vmem_bytes(c: int, d: int, r: int = 1) -> int:
    # two (d, C) point tiles + (C, R) operand/out; generated (C, C) block
    return vmem_bytes([(d, c), (d, c), (c, r), (c, r)], [(c, c), (c, c)])


def batched_kernel_matvec(rows: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
                          kernel_name: str = "gaussian") -> jnp.ndarray:
    """On-the-fly dense kernel-block matvec ``y[b] = phi(rows[b], cols[b]) @ x[b]``.

    Parameters
    ----------
    rows, cols : jnp.ndarray, shape (B, C, d)
        Row / column cluster points per inadmissible leaf block.
    x : jnp.ndarray, shape (B, C)
        Operand slices gathered per block.
    kernel_name : str, optional
        Registered kernel function ("gaussian", "matern").

    Returns
    -------
    y : jnp.ndarray, shape (B, C)
        Per-block products through the matmat kernel as one-column panels;
        the kernel block is generated in VMEM and never materialised in
        HBM (paper §5.4.2).  Leaf sizes whose working set exceeds
        ``VMEM_BUDGET`` fall back to the jnp reference path.
    """
    _, c, d = rows.shape
    if force_ref() or _vmem_bytes(c, d) > VMEM_BUDGET:
        return batched_kernel_matvec_ref(rows, cols, x, kernel_name)
    return batched_kernel_matmat(rows, cols, x[..., None], kernel_name)[..., 0]


def batched_kernel_matmat(rows: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray,
                          kernel_name: str = "gaussian") -> jnp.ndarray:
    """Multi-RHS form ``Y[b] = phi(rows[b], cols[b]) @ X[b]`` (paper §5.4.2).

    Parameters
    ----------
    rows, cols : jnp.ndarray, shape (B, C, d)
        Row / column cluster points per inadmissible leaf block.
    x : jnp.ndarray, shape (B, C, R)
        Panel slices gathered per block.
    kernel_name : str, optional
        Registered kernel function ("gaussian", "matern").

    Returns
    -------
    y : jnp.ndarray, shape (B, C, R)
        Per-block (C, C) @ (C, R) MXU contractions; the kernel block is
        generated once per program and amortised over all R columns.
        Shapes whose working set exceeds ``VMEM_BUDGET`` fall back to the
        jnp reference path.
    """
    _, c, d = rows.shape
    if force_ref() or _vmem_bytes(c, d, x.shape[2]) > VMEM_BUDGET:
        return batched_kernel_matmat_ref(rows, cols, x, kernel_name)
    rows_t = jnp.swapaxes(rows, -1, -2)
    cols_t = jnp.swapaxes(cols, -1, -2)
    return batched_kernel_matmat_t(rows_t, cols_t, x, kernel_name)
