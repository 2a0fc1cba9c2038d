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
PARTS = 1 << 27      # floats of chunk partial sums a row block holds


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


def _pairwise_sum(parts: jnp.ndarray) -> jnp.ndarray:
    """Sum over the leading axis as a balanced tree of float32 adds: its
    rounding grows with the log of the count, not with the count."""
    while parts.shape[0] > 1:
        if parts.shape[0] % 2:
            parts = jnp.concatenate([parts, jnp.zeros_like(parts[:1])])
        half = parts.shape[0] // 2
        parts = parts[:half] + parts[half:]
    return parts[0]


@partial(jax.jit, static_argnames=("kernel", "precision", "chunk"))
def dense_apply(points: jnp.ndarray, x: jnp.ndarray, *, kernel,
                precision: str = "highest", chunk: int = 256) -> jnp.ndarray:
    """``K(points, points) @ x`` for x of shape (n, R), one block of rows
    at a time so that no (n, n) matrix is ever held; ``kernel(y, y')`` is
    a function of two point blocks.

    Each row's sum is one short product per chunk of ``chunk`` columns,
    and the chunks' sums added as a balanced tree.  A float32 sum of n
    terms added in sequence, as a matrix unit accumulates a long
    contraction, rounds by about sqrt(n) ulps of the result: over chunks
    of 4096 columns that is about 3e-6 at n = 2^19 on a TPU v5e, as much
    as the H-matrix's own error.  Short chunks and the tree keep the
    reference's rounding well under the numbers it is compared with;
    the limits were read with this sum."""
    n = points.shape[0]
    nc = -(-n // chunk)
    r = x.shape[1]
    block = max(8, min(512, 1 << max(0, (PARTS // (nc * r)).bit_length() - 1)))
    cols = _row_blocks(points, chunk)            # padding is multiplied by 0
    xc = jnp.zeros((nc * chunk, r), x.dtype).at[:n].set(x)
    xc = xc.reshape(nc, chunk, r)
    blocks = _row_blocks(points, block)

    def one(rows):
        parts = jax.vmap(lambda c, xs: dot(kernel(rows, c), xs, precision))(
            cols, xc)
        return _pairwise_sum(parts)

    out = jax.lax.map(one, blocks).reshape(-1, r)
    return out[:n]


def rel_err(z, ref) -> float:
    """Frobenius ||z - ref|| / ||ref|| in float64 on the host."""
    z = np.asarray(z, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(z - ref) / np.linalg.norm(ref))
