"""Kernel functions of squared distances, usable INSIDE Pallas kernel bodies.

:func:`phi_from_sqdist` is the one definition of each registered kernel's
phi (``repro.core.geometry``'s kernels call it too, so the jnp paths and
the Pallas kernels evaluate the same formula).  The Pallas helpers work on
transposed point layouts ``(d, n)`` so the large axis is the TPU lane
dimension, with the pairwise distance computed by an unrolled loop over
the (tiny, static) point dimension ``d`` — broadcast/subtract/square on
the VPU, no gathers.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

# The TPU's default float32 log errs by up to 1.1e-4 (absolute) and its exp
# by up to 4.8e-6 (relative) on a v5e; that put 3.7e-4 into the Matérn's
# entries and 1.8e-5 into the 3-D model problem's products.  K_1's
# transcendentals ask for the accurate forms, which the CPU computes anyway
# (the same bits there).  Pallas kernel bodies pass ``accuracy=None``: the
# TPU's kernel compiler does not implement the request.
ACCURATE = lax.AccuracyMode.HIGHEST


def pairwise_sqdist_t(rows_t: jnp.ndarray, cols_t: jnp.ndarray) -> jnp.ndarray:
    """rows_t: (d, m), cols_t: (d, n) -> (m, n) squared distances."""
    d = rows_t.shape[0]
    acc = None
    for dim in range(d):
        diff = rows_t[dim][:, None] - cols_t[dim][None, :]
        term = diff * diff
        acc = term if acc is None else acc + term
    return acc


def _bessel_k1(x, accuracy=ACCURATE):
    """Modified Bessel function K_1 via Abramowitz & Stegun 9.8.7 / 9.8.8,
    accurate to about 5e-7 relative in float32 (with accurate log and exp)."""
    small = x <= 2.0
    xs = jnp.where(small, x, 2.0)
    xl = jnp.where(small, 2.0, x)
    t = (xs / 3.75) ** 2
    i1 = xs * (0.5 + t * (0.87890594 + t * (0.51498869 + t * (0.15084934
         + t * (0.02658733 + t * (0.00301532 + t * 0.00032411))))))
    u = (xs / 2.0) ** 2
    p = 1.0 + u * (0.15443144 + u * (-0.67278579 + u * (-0.18156897
        + u * (-0.01919402 + u * (-0.00110404 + u * (-0.00004686))))))
    k1_small = lax.log(xs / 2.0, accuracy=accuracy) * i1 + p / xs
    w = 2.0 / xl
    q = 1.25331414 + w * (0.23498619 + w * (-0.03655620 + w * (0.01504268
        + w * (-0.00780353 + w * (0.00325614 + w * (-0.00068245))))))
    k1_large = lax.exp(-xl, accuracy=accuracy) / jnp.sqrt(xl) * q
    return jnp.where(small, k1_small, k1_large)


def phi_from_sqdist(d2: jnp.ndarray, kernel_name: str, point_dim: int,
                    accuracy=ACCURATE) -> jnp.ndarray:
    """Apply the named kernel to squared distances ``d2 >= 0`` (elementwise,
    VPU): ``gaussian`` exp(-d2), ``matern`` the paper's (§6.2) Matérn with
    beta - d/2 = 1, r K_1(r) / (2^(beta-1) Gamma(beta)), whose value at
    r = 0 is 1 / (2^(beta-1) Gamma(beta))."""
    if kernel_name == "gaussian":
        return jnp.exp(-d2)
    if kernel_name == "matern":
        beta = point_dim / 2.0 + 1.0
        norm = (2.0 ** (beta - 1.0)) * math.gamma(beta)
        r = jnp.sqrt(d2)
        val = jnp.where(r > 1e-8,
                        r * _bessel_k1(jnp.maximum(r, 1e-30), accuracy), 1.0)
        return val / norm
    raise ValueError(f"unknown kernel {kernel_name!r}")
