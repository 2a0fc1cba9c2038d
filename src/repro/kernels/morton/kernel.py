"""Pallas TPU kernel: Morton (Z-order) code computation (paper Alg. 6).

One program per tile of points; the fixed-point quantisation, bit stretch and
dimension interleave are unrolled uint32 shift/or ops on the VPU (<= 63
iterations).  Output is the 64-bit code as two uint32 planes (hi, lo) —
no x64 mode needed; the sort is a lexicographic sort on (hi, lo).

Layout: points arrive lane-major (d, N); tiles of TILE points keep the lane
dimension 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.morton import bits_per_dim

from .. import default_interpret

TILE = 1024


def _kernel(coords_t_ref, hi_ref, lo_ref, *, d: int, nb: int):
    coords_t = coords_t_ref[...]                # (d, TILE)
    scale = jnp.float32(2.0**nb - 1.0)
    q = jnp.clip(coords_t, 0.0, 1.0) * scale    # in [0, 2^nb], nb <= 32
    # the TPU has no float32 -> uint32 convert: truncate the two 16-bit
    # halves separately (both exact in float32, both fit int32), which
    # reproduces the reference's q.astype(uint32) bit for bit
    q_hi = jnp.floor(q * (1.0 / 65536.0))
    q_lo = q - q_hi * 65536.0
    fx = ((q_hi.astype(jnp.int32).astype(jnp.uint32) << jnp.uint32(16))
          + q_lo.astype(jnp.int32).astype(jnp.uint32))
    # float32(2^nb - 1) may round up to 2^nb: clamp like the reference
    fx = jnp.where(q >= 2.0**nb, jnp.uint32(2**nb - 1), fx)
    lo = jnp.zeros((coords_t.shape[1],), jnp.uint32)
    hi = jnp.zeros((coords_t.shape[1],), jnp.uint32)
    one = jnp.uint32(1)
    for b in range(nb):
        for dim in range(d):
            out_pos = b * d + dim
            bit = (fx[dim] >> jnp.uint32(b)) & one
            if out_pos < 32:
                lo = lo | (bit << jnp.uint32(out_pos))
            else:
                hi = hi | (bit << jnp.uint32(out_pos - 32))
    hi_ref[...] = hi
    lo_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("interpret",))
def morton_encode_t(coords_t: jnp.ndarray, interpret: bool | None = None):
    """coords_t: (d, N) with N a multiple of TILE -> (hi, lo) uint32 (N,)."""
    if interpret is None:
        interpret = default_interpret()
    d, n = coords_t.shape
    nb = bits_per_dim(d)
    grid = (n // TILE,)
    return pl.pallas_call(
        functools.partial(_kernel, d=d, nb=nb),
        name="morton_encode",
        grid=grid,
        in_specs=[pl.BlockSpec((d, TILE), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((TILE,), lambda i: (i,)),
                   pl.BlockSpec((TILE,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.uint32)],
        interpret=interpret,
    )(coords_t)
