"""jit'd public wrappers for the batched ACA Pallas kernels.

Implements the paper's ``bs_ACA`` batching-size heuristic for TPU: blocks
whose VMEM working set would overflow the budget (coarse levels with very
large clusters) fall back to the vmapped jnp path; everything else goes
through the Pallas kernels.  ``interpret`` is auto-detected per backend
inside the kernels (compiled on TPU, interpreter elsewhere).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT, force_ref, vmem_bytes

from .kernel import batched_aca_t, batched_lowrank_matmat_t
from .ref import (batched_aca_level_ref, batched_aca_ref,
                  batched_lowrank_matmat_ref)

VMEM_BUDGET = VMEM_LIMIT


def _vmem_bytes(m: int, n: int, d: int, k: int) -> int:
    # lane-major points and factors; U^T/V^T carries plus pivot rows/masks
    return vmem_bytes([(d, m), (d, n), (k, m), (k, n)],
                      [(k, m), (k, n), (8, m), (8, n)])


def _lowrank_vmem_bytes(m: int, n: int, k: int, r: int) -> int:
    return vmem_bytes([(m, k), (n, k), (n, r), (m, r)], [(k, r)])


def batched_aca_pallas(rows: jnp.ndarray, cols: jnp.ndarray,
                       kernel_name: str, k: int):
    """Batched fixed-rank ACA factorization of admissible blocks (§5.4.1).

    Parameters
    ----------
    rows : jnp.ndarray, shape (B, m, d)
        Row cluster points per admissible block of one level group.
    cols : jnp.ndarray, shape (B, n, d)
        Column cluster points per block.
    kernel_name : str
        Registered kernel function ("gaussian", "matern").
    k : int
        Fixed ACA rank.

    Returns
    -------
    U : jnp.ndarray, shape (B, m, k)
    V : jnp.ndarray, shape (B, n, k)
        Low-rank factors with ``phi(rows[b], cols[b]) ~= U[b] @ V[b].T``.
        Blocks whose working set exceeds ``VMEM_BUDGET`` (coarse levels
        with very large clusters — the paper's ``bs_ACA`` batching-size
        heuristic) fall back to the vmapped jnp oracle.
    """
    b, m, d = rows.shape
    n = cols.shape[1]
    if force_ref() or _vmem_bytes(m, n, d, k) > VMEM_BUDGET:
        return batched_aca_ref(rows, cols, kernel_name, k)
    rows_t = jnp.swapaxes(rows, -1, -2)
    cols_t = jnp.swapaxes(cols, -1, -2)
    return batched_aca_t(rows_t, cols_t, kernel_name, k)


def batched_aca_level(points: jnp.ndarray, row_ids: jnp.ndarray,
                      col_ids: jnp.ndarray, level: int,
                      kernel_name: str, k: int):
    """Construction entry point: factor ONE admissible level group.

    The device-build pipeline (``core.build_device``) calls this once per
    level — the cluster-point gather happens here, device-side, from the
    tree-ordered point array, so factor assembly is O(levels) launches
    with no host-staged coordinate batches.

    Parameters
    ----------
    points : jnp.ndarray, shape (n_pad, d)
        Tree-ordered (Morton-sorted, padded) coordinates.
    row_ids, col_ids : jnp.ndarray, shape (B,)
        Row/column cluster ids of the level group's admissible blocks.
    level : int
        Tree level (cluster ``i`` spans rows ``[i*m, (i+1)*m)`` with
        ``m = n_pad >> level``).
    kernel_name : str
        Registered kernel function ("gaussian", "matern").
    k : int
        Fixed ACA rank.

    Returns
    -------
    U : jnp.ndarray, shape (B, m, k)
    V : jnp.ndarray, shape (B, m, k)
        Low-rank factors per block.  Level groups whose per-block working
        set exceeds ``VMEM_BUDGET`` (coarse levels — the paper's
        ``bs_ACA`` heuristic) fall back to ``batched_aca_level_ref``.
    """
    n_pad, d = points.shape
    m = n_pad >> level
    if force_ref() or _vmem_bytes(m, m, d, k) > VMEM_BUDGET:
        return batched_aca_level_ref(points, row_ids, col_ids, level,
                                     kernel_name, k)
    pts = points.reshape(1 << level, m, d)
    rows_t = jnp.swapaxes(pts[row_ids], -1, -2)
    cols_t = jnp.swapaxes(pts[col_ids], -1, -2)
    return batched_aca_t(rows_t, cols_t, kernel_name, k)


def batched_lowrank_matmat(u: jnp.ndarray, v: jnp.ndarray,
                           x: jnp.ndarray) -> jnp.ndarray:
    """Low-rank apply ``Y[b] = U[b] @ (V[b]^T @ X[b])`` in multi-RHS form.

    Parameters
    ----------
    u : jnp.ndarray, shape (B, m, k)
    v : jnp.ndarray, shape (B, n, k)
        ACA factors of one admissible level group.
    x : jnp.ndarray, shape (B, n, R)
        Panel slices gathered per block.

    Returns
    -------
    y : jnp.ndarray, shape (B, m, R)
        Two (k-thin) MXU contractions per block, amortised over all R
        columns.  Blocks whose panels would overflow ``VMEM_BUDGET`` fall
        back to the jnp einsum path.
    """
    b, m, k = u.shape
    n = v.shape[1]
    r = x.shape[2]
    if force_ref() or _lowrank_vmem_bytes(m, n, k, r) > VMEM_BUDGET:
        return batched_lowrank_matmat_ref(u, v, x)
    return batched_lowrank_matmat_t(u, v, x)
