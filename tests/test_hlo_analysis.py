"""HLO analyzer: trip-count-adjusted FLOPs/collectives on known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo, parse_module
from repro.analysis.roofline import roofline_terms


def test_dot_flops_simple_matmul():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    stats = analyze_hlo(compiled.as_text())
    assert stats.dot_flops == 2 * 64 * 128 * 32


def test_scan_trip_count_multiplies_flops():
    w = jnp.zeros((5, 32, 32), jnp.float32)
    x = jnp.zeros((8, 32), jnp.float32)

    def f(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        out, _ = jax.lax.scan(body, x, w)
        return out

    compiled = jax.jit(f).lower(w, x).compile()
    stats = analyze_hlo(compiled.as_text())
    expected = 5 * 2 * 8 * 32 * 32
    assert abs(stats.dot_flops - expected) / expected < 0.01
    assert any(l["trip"] == 5 for l in stats.loops)


def test_nested_scan_trips_compound():
    w = jnp.zeros((3, 4, 16, 16), jnp.float32)
    x = jnp.zeros((2, 16), jnp.float32)

    def f(w, x):
        def outer(x, wo):
            def inner(x, wi):
                return x @ wi, None
            x, _ = jax.lax.scan(inner, x, wo)
            return x, None
        out, _ = jax.lax.scan(outer, x, w)
        return out

    compiled = jax.jit(f).lower(w, x).compile()
    stats = analyze_hlo(compiled.as_text())
    expected = 3 * 4 * 2 * 2 * 16 * 16
    assert abs(stats.dot_flops - expected) / expected < 0.01


def test_parse_module_computations():
    compiled = jax.jit(lambda x: jnp.tanh(x).sum()).lower(
        jnp.zeros((8, 8))).compile()
    comps = parse_module(compiled.as_text())
    assert "__entry__" in comps and len(comps) >= 1


def test_traffic_nonzero_for_dot():
    a = jnp.zeros((256, 256), jnp.float32)
    compiled = jax.jit(lambda a: a @ a).lower(a).compile()
    stats = analyze_hlo(compiled.as_text())
    assert stats.traffic_bytes >= 3 * 256 * 256 * 4  # two reads + one write


def test_roofline_terms_dominance():
    t = roofline_terms(flops_per_chip=197e12, hbm_bytes_per_chip=1.0,
                       collective_bytes_per_chip=1.0, model_flops_per_chip=197e12,
                       device_kind="TPU v5 lite")
    assert t.dominant == "compute" and abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.roofline_fraction - 1.0) < 1e-6
    t2 = roofline_terms(1.0, 819e9, 1.0, device_kind="TPU v5 lite")
    assert t2.dominant == "memory" and abs(t2.memory_s - 1.0) < 1e-9


def test_roofline_unknown_device_raises():
    """A device without a peak table is an error, never v5e's peaks."""
    with pytest.raises(ValueError, match="cpu"):
        roofline_terms(1.0, 1.0, 1.0, device_kind="cpu")
