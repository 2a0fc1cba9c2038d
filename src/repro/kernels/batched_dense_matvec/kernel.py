"""Pallas TPU kernel: batched fused kernel-evaluation + dense matmat.

The paper's §5.4.2 batched dense sub-matrix application (MAGMA
``magmablas_dgemv_vbatched`` on GPU).  TPU adaptation (DESIGN.md §3.3):

  * ragged batches -> every inadmissible leaf block is exactly
    (C_leaf x C_leaf) by balanced CBC, so the batch is perfectly regular;
  * the matrix entries are *generated in VMEM* from the point coordinates
    (phi(y_i, y_j)) and consumed immediately by the MXU matvec — the block is
    never written to HBM (the paper's "dense blocks are never precomputed"
    taken one level further: they never even exist in main memory).

One generated block is applied to R right-hand sides at once: the MXU
contraction is (C, C) @ (C, R), so the kernel entries are generated ONCE
per block and amortised over all R columns.  A single vector is the R = 1
panel (a ``(B, C, 1)`` operand keeps the block's last two dimensions equal
to the array's, which the TPU's tiling rule requires).

Grid: one program per block b.
VMEM working set per program (C = C_leaf, d = point dim, f32):
    rows_t, cols_t : 2 * d * C * 4 B           (points, lane-major)
    X, Y           : 2 * C * R * 4 B
    A              : C * C * 4 B               (generated scores)
  C=512, d=3, R=64: ~1.3 MB  << 16 MB VMEM.  C and the MXU contraction dim
  are multiples of 128 for C_leaf in {128, 256, 512}.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import default_interpret
from .._phi import pairwise_sqdist_t, phi_from_sqdist


def _kernel_mm(rows_t_ref, cols_t_ref, x_ref, y_ref, *, kernel_name: str,
               point_dim: int):
    rows_t = rows_t_ref[0]            # (d, C)
    cols_t = cols_t_ref[0]            # (d, C)
    x = x_ref[0]                      # (C, R)
    d2 = pairwise_sqdist_t(rows_t, cols_t)            # (C, C)  VPU
    a = phi_from_sqdist(d2, kernel_name, point_dim,   # (C, C)  VPU
                        accuracy=None)
    y_ref[0] = jnp.dot(a, x, preferred_element_type=jnp.float32)  # MXU


@functools.partial(jax.jit, static_argnames=("kernel_name", "interpret"))
def batched_kernel_matmat_t(rows_t: jnp.ndarray, cols_t: jnp.ndarray,
                            x: jnp.ndarray, kernel_name: str = "gaussian",
                            interpret: bool | None = None) -> jnp.ndarray:
    """Y[b] = phi(rows[b], cols[b]) @ X[b].

    rows_t, cols_t: (B, d, C) lane-major points; x: (B, C, R) -> (B, C, R).
    """
    if interpret is None:
        interpret = default_interpret()
    b, d, c = rows_t.shape
    r = x.shape[2]
    grid = (b,)
    return pl.pallas_call(
        functools.partial(_kernel_mm, kernel_name=kernel_name, point_dim=d),
        name="batched_kernel_matmat",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, d, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, r), x.dtype),
        interpret=interpret,
    )(rows_t, cols_t, x)
