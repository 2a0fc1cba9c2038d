"""Halton points on a square (the design of the paper's section 6), made on
the device.  Configuration keys: ``n_points``, ``dim``, ``side``."""
import math
from functools import partial

import jax
import jax.numpy as jnp

_PRIMES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29)


def _radical_inverse(idx, base: int, n_digits: int):
    result = jnp.zeros(idx.shape, jnp.float32)
    f = inv = 1.0 / base
    for _ in range(n_digits):
        result = result + (idx % base).astype(jnp.float32) * f
        idx = idx // base
        f = f * inv
    return result


@partial(jax.jit, static_argnames=("n", "d"))
def halton(n: int, d: int):
    """First ``n`` points of the ``d``-dimensional Halton sequence, (n, d)
    float32."""
    idx = jnp.arange(1, n + 1, dtype=jnp.int32)
    n_digits = max(8, int(math.ceil(math.log(n + 1) / math.log(2))) + 1)
    return jnp.stack([_radical_inverse(idx, _PRIMES[j], n_digits)
                      for j in range(d)], axis=-1)


def points(cfg: dict):
    pts = halton(cfg["n_points"], cfg["dim"])
    return pts * cfg["side"] if cfg["side"] != 1.0 else pts
