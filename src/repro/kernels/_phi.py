"""Kernel functions of squared distances, usable INSIDE Pallas kernel bodies.

:func:`phi_from_sqdist` is the one definition of each registered kernel's
phi (``repro.core.geometry``'s kernels call it too, so the jnp paths and
the Pallas kernels evaluate the same formula).  The Pallas helpers work on
transposed point layouts ``(d, n)`` so the large axis is the TPU lane
dimension, with the pairwise distance computed by an unrolled loop over
the (tiny, static) point dimension ``d`` — broadcast/subtract/square on
the VPU, no gathers.

A kernel whose phi switches expression at some distances declares them
in :data:`BRANCH_POINTS`: the Matérn's K_1 changes at r = 2 (Abramowitz &
Stegun 9.8.7 below, 9.8.8 above), the Gaussian never.  Given a static
``r_range=(lo, hi)`` that bounds every distance it will see,
:func:`phi_from_sqdist` evaluates only the expressions that range
reaches; without one it evaluates each and selects per entry.  The
near-field apply (``repro.core.hmatrix``) passes each block group's range
from the leaf bounding boxes.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
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


# K_1's branch point, and the distances at which each kernel's phi switches
# expression.  A range keeps clear of a branch point by BRANCH_MARGIN, so
# float32 rounding of r cannot carry an entry across it against the float64
# bounds the range was drawn from.
K1_BRANCH = 2.0
BRANCH_MARGIN = 1e-3
BRANCH_POINTS = {"gaussian": (), "matern": (K1_BRANCH,)}


def branch_side(lo, hi, point: float):
    """-1 where ``[lo, hi]`` lies below ``point`` by the margin, +1 where it
    lies above, 0 where it may reach both sides (elementwise on arrays)."""
    above = np.asarray(lo) >= point + BRANCH_MARGIN
    below = np.asarray(hi) <= point - BRANCH_MARGIN
    return above.astype(np.int8) - below.astype(np.int8)


def _k1_small(x, accuracy):
    """A&S 9.8.7, K_1 for 0 < x <= 2."""
    t = (x / 3.75) ** 2
    i1 = x * (0.5 + t * (0.87890594 + t * (0.51498869 + t * (0.15084934
         + t * (0.02658733 + t * (0.00301532 + t * 0.00032411))))))
    u = (x / 2.0) ** 2
    p = 1.0 + u * (0.15443144 + u * (-0.67278579 + u * (-0.18156897
        + u * (-0.01919402 + u * (-0.00110404 + u * (-0.00004686))))))
    return lax.log(x / 2.0, accuracy=accuracy) * i1 + p / x


def _k1_large(x, accuracy):
    """A&S 9.8.8, K_1 for x >= 2."""
    w = 2.0 / x
    q = 1.25331414 + w * (0.23498619 + w * (-0.03655620 + w * (0.01504268
        + w * (-0.00780353 + w * (0.00325614 + w * (-0.00068245))))))
    return lax.exp(-x, accuracy=accuracy) / jnp.sqrt(x) * q


def _bessel_k1(x, accuracy=ACCURATE, x_range=None):
    """Modified Bessel function K_1 via Abramowitz & Stegun 9.8.7 (x <= 2) /
    9.8.8 (x > 2), accurate to about 5e-7 relative in float32 (with
    accurate log and exp).

    ``x_range=(lo, hi)``, static, bounds x: a range clear of the branch
    point x = 2 (by :data:`BRANCH_MARGIN`) evaluates that side's expression
    alone, on the same bits; None, or a range that reaches both sides,
    evaluates both and selects per entry."""
    side = 0 if x_range is None else branch_side(*x_range, K1_BRANCH)
    if side < 0:
        return _k1_small(x, accuracy)
    if side > 0:
        return _k1_large(x, accuracy)
    small = x <= K1_BRANCH
    xs = jnp.where(small, x, K1_BRANCH)
    xl = jnp.where(small, K1_BRANCH, x)
    return jnp.where(small, _k1_small(xs, accuracy), _k1_large(xl, accuracy))


def phi_from_sqdist(d2: jnp.ndarray, kernel_name: str, point_dim: int,
                    accuracy=ACCURATE, r_range=None) -> jnp.ndarray:
    """Apply the named kernel to squared distances ``d2 >= 0`` (elementwise,
    VPU): ``gaussian`` exp(-d2), ``matern`` the paper's (§6.2) Matérn with
    beta - d/2 = 1, r K_1(r) / (2^(beta-1) Gamma(beta)), whose value at
    r = 0 is 1 / (2^(beta-1) Gamma(beta)).  ``r_range=(lo, hi)``, static,
    bounds r = sqrt(d2): K_1 then evaluates only the branches it reaches
    (the Gaussian has none to skip)."""
    if kernel_name == "gaussian":
        return jnp.exp(-d2)
    if kernel_name == "matern":
        beta = point_dim / 2.0 + 1.0
        norm = (2.0 ** (beta - 1.0)) * math.gamma(beta)
        r = jnp.sqrt(d2)
        val = jnp.where(r > 1e-8,
                        r * _bessel_k1(jnp.maximum(r, 1e-30), accuracy,
                                       r_range), 1.0)
        return val / norm
    raise ValueError(f"unknown kernel {kernel_name!r}")
