"""Point sets and kernel functions for the paper's model problem (§6.2).

The paper benchmarks collocation matrices  A[i, j] = phi(y_i, y_j)  where
``Y`` is a Halton sequence on [0, 1]^d and ``phi`` is the (unscaled) Gaussian
kernel or a Matérn kernel with ``beta - d/2 = 1`` (i.e. ``r * K_1(r)`` up to a
constant).  Everything here is pure JAX so it runs inside jit/vmap/pallas
reference paths.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from ..kernels._phi import BRANCH_POINTS, phi_from_sqdist

# ---------------------------------------------------------------------------
# Halton sequences (quasi Monte-Carlo), as used for the paper's point sets.
# ---------------------------------------------------------------------------

_PRIMES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29)


def _radical_inverse(indices: jnp.ndarray, base: int, n_digits: int) -> jnp.ndarray:
    """Vectorised radical inverse of ``indices`` in ``base``.

    ``n_digits`` is static; 40 digits of base 2 covers N up to 2^40.
    """
    idx = indices.astype(jnp.uint64) if indices.dtype == jnp.uint64 else indices.astype(jnp.int64) if jax.config.jax_enable_x64 else indices.astype(jnp.int32)
    result = jnp.zeros(indices.shape, jnp.float32)
    inv_base = 1.0 / base
    f = inv_base
    for _ in range(n_digits):
        digit = (idx % base).astype(jnp.float32)
        result = result + digit * f
        idx = idx // base
        f = f * inv_base
    return result


def halton(n: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    """First ``n`` points of the ``d``-dimensional Halton sequence in [0,1]^d."""
    if d > len(_PRIMES):
        raise ValueError(f"halton supports d <= {len(_PRIMES)}")
    idx = jnp.arange(1, n + 1)
    n_digits = max(8, int(math.ceil(math.log(n + 1) / math.log(2))) + 1)
    cols = [_radical_inverse(idx, _PRIMES[j], n_digits) for j in range(d)]
    return jnp.stack(cols, axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# Kernel functions phi(y, y')
# ---------------------------------------------------------------------------


def _sqdist(y: jnp.ndarray, yp: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared distances between (..., m, d) and (..., n, d)."""
    # Summed squared coordinate differences, one (m, n) term per (tiny,
    # static) dimension, as kernels/_phi.py does: exactly zero on the
    # diagonal and symmetric.  The expansion |a|^2 + |b|^2 - 2 a.b cancels
    # catastrophically away from the origin: on Halton points scaled to
    # side 128 it moves entries by up to 6.6e-3, and 631 of 1024 diagonal
    # leaf blocks shifted by sigma2 = 1e-2 stop being positive definite.
    acc = None
    for dim in range(y.shape[-1]):
        diff = y[..., :, None, dim] - yp[..., None, :, dim]
        acc = diff * diff if acc is None else acc + diff * diff
    return acc


def gaussian_kernel(y: jnp.ndarray, yp: jnp.ndarray) -> jnp.ndarray:
    """phi_G(y, y') = exp(-||y - y'||^2)   (paper §6.2, unscaled)."""
    return jnp.exp(-_sqdist(y, yp))


def matern_kernel(y: jnp.ndarray, yp: jnp.ndarray, d: int | None = None,
                  r_range: tuple[float, float] | None = None) -> jnp.ndarray:
    """Matérn kernel with ``beta - d/2 = 1`` (paper §6.2).

    phi_M(y,y') = K_1(r) r / (2^(beta-1) Gamma(beta)),  beta = d/2 + 1.
    ``r * K_1(r) -> 1`` as ``r -> 0`` so the diagonal is finite.  The
    formula and its Bessel K_1 are ``kernels/_phi.phi_from_sqdist``'s, which
    the Pallas kernels evaluate too.  ``r_range=(lo, hi)``, static, bounds
    every distance between ``y`` and ``yp``: K_1 then evaluates only the
    branches it reaches (``matern_kernel.branch_points``).
    """
    return phi_from_sqdist(_sqdist(y, yp), "matern",
                           y.shape[-1] if d is None else d, r_range=r_range)


# The distances at which a kernel switches expression: a kernel with any
# takes ``r_range=`` (see ``kernels/_phi.py``).
gaussian_kernel.branch_points = BRANCH_POINTS["gaussian"]
matern_kernel.branch_points = BRANCH_POINTS["matern"]

KERNELS: dict[str, Callable] = {
    "gaussian": gaussian_kernel,
    "matern": matern_kernel,
}


def get_kernel(name: str) -> Callable:
    if name not in KERNELS:
        raise KeyError(f"unknown kernel {name!r}; have {sorted(KERNELS)}")
    return KERNELS[name]


def dense_kernel_matrix(points: jnp.ndarray, kernel: Callable | str = "gaussian",
                        points_b: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle: the full dense collocation matrix (test/bench use only)."""
    if isinstance(kernel, str):
        kernel = get_kernel(kernel)
    pb = points if points_b is None else points_b
    return kernel(points, pb)


_TARGET_FREQS = ((4.0, 3.0), (2.0, 5.0), (6.0, 1.0), (3.0, 3.0),
                 (5.0, 2.0), (1.0, 6.0), (4.0, 4.0), (2.0, 2.0))


def sinusoid_targets(pts: jnp.ndarray, r: int, domain: float = 1.0) -> jnp.ndarray:
    """Family of R regression targets f_j(y) = sin(a_j y_0) cos(b_j y_1).

    The model regression problem of the kernel-ridge demo/benchmarks:
    2-D points on a domain of side ``domain`` -> (N, R) f32 target panel
    (frequencies cycle through a fixed 8-entry table).
    """
    import numpy as np
    y = np.asarray(pts)
    freqs = (_TARGET_FREQS * ((r + len(_TARGET_FREQS) - 1)
                              // len(_TARGET_FREQS)))[:r]
    cols = [np.sin(a * y[:, 0] / domain) * np.cos(b * y[:, 1] / domain)
            for a, b in freqs]
    return jnp.asarray(np.stack(cols, axis=1).astype(np.float32))
