"""jit'd wrapper for the Morton encode Pallas kernel (pads to the tile)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT, vmem_bytes

from .kernel import TILE, morton_encode_t
from .ref import morton_encode_ref

VMEM_BUDGET = VMEM_LIMIT


def _vmem_bytes(d: int) -> int:
    # one (d, TILE) coordinate tile plus the hi/lo uint32 output lanes and
    # the per-dimension interleave scratch
    return vmem_bytes([(d, TILE), (TILE,), (TILE,)], [(d, TILE)] * 2)


def morton_encode_pallas(coords: jnp.ndarray):
    """64-bit Morton (Z-order) codes of a point set (paper §4.4).

    Parameters
    ----------
    coords : jnp.ndarray, shape (N, d)
        Points in the unit box ``[0, 1]^d`` (out-of-range coordinates clip
        to the boundary code).

    Returns
    -------
    hi, lo : jnp.ndarray, uint32, shape (N,)
        High and low 32-bit halves of each 64-bit interleaved code.  The
        lane dimension is padded to a multiple of ``TILE`` for the kernel
        and sliced back before returning.  Dimensions whose working set
        exceeds ``VMEM_BUDGET`` fall back to the jnp reference path.
    """
    n, d = coords.shape
    if _vmem_bytes(d) > VMEM_BUDGET:
        return morton_encode_ref(coords)
    n_pad = ((n + TILE - 1) // TILE) * TILE
    coords_t = jnp.swapaxes(coords, 0, 1)
    if n_pad != n:
        coords_t = jnp.pad(coords_t, ((0, 0), (0, n_pad - n)))
    hi, lo = morton_encode_t(coords_t)
    return hi[:n], lo[:n]
