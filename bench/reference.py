"""Plain reference of the benchmark's deployments.

Imports nothing of the library under test and takes nothing it made:
dense kernel products in row blocks, with the kernel of
``bench/kernels/<name>.py`` on points of ``bench/points/<name>.py``.
Every product here states its matmul precision; the benchmark reads them
at ``HIGHEST`` (float32), and the controls at ``high``, the next
precision down: three bfloat16 passes, written out (:func:`dot`) so that
they round alike on every backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _split_hi(a: jnp.ndarray) -> jnp.ndarray:
    """The top 16 bits of each float32: exact in bfloat16.  Made with a
    bit mask, which no compiler folds away (a round trip through
    bfloat16 may be folded to the identity on the TPU)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def dot(a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``a @ b`` in float32 at ``highest``, or as the TPU's ``high`` does
    it (three bfloat16 passes, hi*hi + hi*lo + lo*hi, of the split
    a = hi + lo), or ``default`` (one pass), with each pass's products
    exact and summed in float32."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HIGHEST)
    if precision not in ("high", "default"):
        raise ValueError(f"unknown precision {precision!r}")
    bf, f32 = jnp.bfloat16, jnp.float32

    def one_pass(x, y):
        return jnp.dot(x.astype(bf), y.astype(bf), preferred_element_type=f32)

    if precision == "default":
        return one_pass(a, b)
    a1, b1 = _split_hi(a), _split_hi(b)
    return one_pass(a1, b1) + one_pass(a1, b - b1) + one_pass(a - a1, b1)


def _row_blocks(points: jnp.ndarray, block: int):
    n, d = points.shape
    nb = -(-n // block)
    pad = jnp.broadcast_to(points[-1:], (nb * block - n, d))
    return jnp.concatenate([points, pad]).reshape(nb, block, d)


@partial(jax.jit, static_argnames=("kernel", "precision", "block", "chunk"))
def dense_apply(points: jnp.ndarray, x: jnp.ndarray, *, kernel,
                precision: str = "highest", block: int = 512,
                chunk: int = 4096) -> jnp.ndarray:
    """``K(points, points) @ x`` for x of shape (n, R), one block of rows
    at a time so that no (n, n) matrix is ever held; ``kernel(y, y')`` is
    a function of two point blocks.

    Each row's sum is split over chunks of ``chunk`` columns: one product
    per chunk, and the chunks' sums added after.  The limits were read
    with this order of summation, and it matters at their scale: on a TPU
    v5e at n = 2^19, float32 references that differ only in that order
    differ by about 4e-6, as much as the H-matrix differs from either."""
    n = points.shape[0]
    nc = -(-n // chunk)
    cols = _row_blocks(points, chunk)            # padding is multiplied by 0
    xc = jnp.zeros((nc * chunk, x.shape[1]), x.dtype).at[:n].set(x)
    xc = xc.reshape(nc, chunk, x.shape[1])
    blocks = _row_blocks(points, block)

    def one(rows):
        parts = jax.vmap(lambda c, xs: dot(kernel(rows, c), xs, precision))(
            cols, xc)
        return jnp.sum(parts, axis=0)

    out = jax.lax.map(one, blocks).reshape(-1, x.shape[1])
    return out[:n]


def rel_err(z, ref) -> float:
    """Frobenius ||z - ref|| / ||ref|| in float64 on the host."""
    z = np.asarray(z, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(z - ref) / np.linalg.norm(ref))
