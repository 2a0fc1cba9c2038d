"""Shared kernel-package helpers.

Each package holds ``kernel.py`` (the Pallas kernel), ``ref.py`` (its jnp
oracle) and ``ops.py`` (the dispatcher, which sends a shape whose VMEM
working set exceeds the package's ``VMEM_BUDGET`` to the oracle).
"""
from __future__ import annotations

import math
import os

import jax

# The TPU kernel compiler's scoped VMEM limit is 16 MiB per program on
# v5e; keep a quarter of it for the compiler's own scratch.
VMEM_LIMIT = 12 * 1024 * 1024


def tile_bytes(shape, itemsize: int = 4) -> int:
    """Bytes of one VMEM buffer of ``shape``: the last two dimensions are
    padded to the (8, 128) sublane x lane tile."""
    dims = (1,) * (2 - len(shape)) + tuple(shape)
    lead = math.prod(dims[:-2])
    return (lead * (-(-dims[-2] // 8) * 8) * (-(-dims[-1] // 128) * 128)
            * itemsize)


def vmem_bytes(blocks, temps=(), itemsize: int = 4) -> int:
    """VMEM of one kernel program: its pipelined ``blocks`` (inputs and
    outputs, double-buffered) plus the in-body ``temps``."""
    return (2 * sum(tile_bytes(b, itemsize) for b in blocks)
            + sum(tile_bytes(t, itemsize) for t in temps))


def force_ref() -> bool:
    """Degraded-mode switch: ``REPRO_FORCE_REF=1`` routes every kernel
    dispatcher to its jnp reference path.

    The resilience layer's last-resort knob: if Pallas kernels themselves
    are suspected (miscompiles, NaN-producing lowering bugs), an operator
    can flip the whole fleet to the slower-but-trusted oracle without a
    code change.  Read per call so tests can monkeypatch the environment.
    """
    return os.environ.get("REPRO_FORCE_REF", "0") not in ("", "0")


def default_interpret() -> bool:
    """Pallas ``interpret`` default: compiled on TPU, interpreter elsewhere.

    Every kernel entry point takes ``interpret: bool | None = None`` and
    resolves ``None`` through this helper, so real hardware runs compiled
    kernels while CPU tests/CI transparently use the interpreter.
    """
    return jax.default_backend() != "tpu"
