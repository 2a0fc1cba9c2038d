"""Compile-only rehearsal of the main-path Pallas kernels for a TPU v5e.

Every other kernel test runs in interpret mode, which accepts programs the
TPU's kernel compiler refuses (value dynamic slices, unaligned blocks,
unsupported casts).  These tests lower and compile each kernel on the
apply / build / solve path with ``interpret=False`` for a described — not
attached — ``v5e:2x2`` topology, at the widths of the paper's model
problem (``c_leaf`` = 256, d = 2, k = 16, R in {1, 64}).  Nothing runs:
they check that the chip's compiler accepts each kernel and that the
program carries it as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so every xdist worker must be
able to collect this file without touching it.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_aca.kernel import (batched_aca_t,
                                              batched_lowrank_matmat_t)
from repro.kernels.batched_block_solve.kernel import (
    batched_block_cholesky_solve_t, batched_block_cholesky_t)
from repro.kernels.batched_dense_matvec.kernel import batched_kernel_matmat_t
from repro.kernels.morton.kernel import morton_encode_t

C_LEAF, D, K, B = 256, 2, 16, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, name, one_chip, *shapes, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    assert "tpu_custom_call" in text and f'kernel_name = "{name}"' in text
    return lowered.compile()


@pytest.mark.parametrize("r", [1, 64])
def test_dense_leaf_matmat_compiles(one_chip, r):
    _compile(partial(batched_kernel_matmat_t, kernel_name="gaussian",
                     interpret=False), "batched_kernel_matmat", one_chip,
             (B, D, C_LEAF), (B, D, C_LEAF), (B, C_LEAF, r))


@pytest.mark.parametrize("m", [C_LEAF, 16 * C_LEAF])
@pytest.mark.parametrize("r", [1, 64])
def test_lowrank_matmat_compiles(one_chip, m, r):
    _compile(partial(batched_lowrank_matmat_t, interpret=False),
             "batched_lowrank_matmat", one_chip, (B, m, K), (B, m, K),
             (B, m, r))


@pytest.mark.parametrize("m", [C_LEAF, 16 * C_LEAF])
def test_aca_level_kernel_compiles(one_chip, m):
    _compile(partial(batched_aca_t, kernel_name="gaussian", k=K,
                     interpret=False), "batched_aca", one_chip,
             (B, D, m), (B, D, m))


def test_block_cholesky_compiles(one_chip):
    _compile(partial(batched_block_cholesky_t, interpret=False),
             "batched_block_cholesky", one_chip, (B, C_LEAF, C_LEAF))


@pytest.mark.parametrize("r", [1, 64])
def test_block_cholesky_solve_compiles(one_chip, r):
    _compile(partial(batched_block_cholesky_solve_t, interpret=False),
             "batched_block_cholesky_solve", one_chip,
             (B, C_LEAF, C_LEAF), (B, C_LEAF, r))


def test_morton_compiles(one_chip):
    _compile(partial(morton_encode_t, interpret=False), "morton_encode",
             one_chip, (D, 1 << 18))
