"""The near field regenerates only the K_1 branches its blocks can reach.

Each dense block carries bounds ``[r_lo, r_hi]`` on its pairwise distances
(from the leaf boxes, in both plan builders); the apply groups the blocks
by the side of K_1's branch point x = 2 that their range lies on, and each
group evaluates that side's expression alone, on the same bits."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special

from repro.core import (build_hmatrix, build_hmatrix_device_report, halton,
                        hmatrix, make_apply)
from repro.kernels._phi import BRANCH_MARGIN, K1_BRANCH, _bessel_k1

SMALL = (0.0, K1_BRANCH - BRANCH_MARGIN)
LARGE = (K1_BRANCH + BRANCH_MARGIN, 40.0)


def _k1_inputs(x_range, seed):
    lo, hi = x_range
    x = np.random.default_rng(seed).uniform(max(lo, 1e-6), hi, 1 << 16)
    edge = [hi, 1e-30, 1e-8] if x_range == SMALL else [lo]
    return jnp.asarray(np.concatenate([x, edge]), jnp.float32)


@pytest.mark.parametrize("x_range", [SMALL, LARGE, (1.5, 2.5)],
                         ids=["small", "large", "both"])
def test_k1_range_keeps_the_bits(x_range):
    """K_1 given a static range equals the two-branch K_1 bit for bit on
    inputs in that range, its ends and the guard's tiny x included."""
    x = _k1_inputs(x_range, seed=18)
    both = jax.jit(_bessel_k1)(x)
    ranged = jax.jit(lambda v: _bessel_k1(v, x_range=x_range))(x)
    assert np.array_equal(np.asarray(ranged), np.asarray(both))


def test_k1_range_drops_the_unreached_transcendental():
    """The small branch alone asks for no exp, the large alone for no log."""
    x = jnp.ones((8,), jnp.float32)

    def prims(x_range):
        jaxpr = jax.make_jaxpr(lambda v: _bessel_k1(v, x_range=x_range))(x)
        return {e.primitive.name for e in jaxpr.eqns}
    assert "log" in prims(SMALL) and "exp" not in prims(SMALL)
    assert "exp" in prims(LARGE) and "log" not in prims(LARGE)
    assert {"exp", "log"} <= prims(None)


def _brute_ranges(hm):
    """Exact min and max pairwise distance of every dense block (float64)."""
    c = hm.plan.c_leaf
    pts = np.asarray(hm.tree.points, np.float64).reshape(-1, c,
                                                         hm.tree.points.shape[1])
    out = []
    for i, j in hm.plan.dense_blocks:
        r = np.sqrt(((pts[i][:, None] - pts[j][None]) ** 2).sum(-1))
        out.append((r.min(), r.max()))
    return np.asarray(out)


@pytest.mark.parametrize("d", [2, 3])
def test_dense_ranges_bound_every_pair(d):
    """Both plan builders give every dense block bounds that hold each of
    its pairwise distances, and the same bounds."""
    pts = jnp.asarray(halton(1 << 12, d)) * 4.0
    host = build_hmatrix(pts, kernel="matern", c_leaf=128)
    dev, _ = build_hmatrix_device_report(pts, kernel="matern", c_leaf=128)
    np.testing.assert_array_equal(host.plan.dense_range, dev.plan.dense_range)
    rng = dev.plan.dense_range
    assert rng.shape == (dev.plan.num_dense_blocks, 2)
    brute = _brute_ranges(dev)
    slack = 1e-12 * brute[:, 1].max()
    assert np.all(rng[:, 0] <= brute[:, 0] + slack)
    assert np.all(brute[:, 1] <= rng[:, 1] + slack)
    assert np.all(rng[:, 0] <= rng[:, 1])


def _matern64(y, yp):
    d = y.shape[1]
    beta = d / 2 + 1
    r = np.sqrt(((y[:, None, :] - yp[None, :, :]) ** 2).sum(-1))
    with np.errstate(invalid="ignore"):
        val = np.where(r > 0, r * scipy.special.k1(r), 1.0)
    return val / (2 ** (beta - 1) * math.gamma(beta))


# Halton designs whose near field falls in all three groups: small leaves
# (diagonal blocks within r 2), and a small eta, which keeps blocks whose
# leaves lie more than 2 apart in the near field.
@pytest.mark.parametrize("d,side", [(2, 8.0), (3, 6.0)])
def test_three_groups_match_float64(d, side):
    pts = jnp.asarray(halton(1 << 12, d)) * side
    hm, report = build_hmatrix_device_report(pts, kernel="matern", k=16,
                                             c_leaf=16, eta=0.5,
                                             precompute=True)
    groups = hmatrix.near_field_groups(hm.plan, hm.kernel)
    sizes = [len(np.arange(hm.plan.num_dense_blocks)[sel])
             for sel, _ in groups]
    ranges = [r for _, r in groups]
    assert len(groups) == 3 and min(sizes) > 0
    assert ranges[0][1] <= K1_BRANCH - BRANCH_MARGIN          # small only
    assert ranges[2][0] >= K1_BRANCH + BRANCH_MARGIN          # large only
    assert report.near_field_pruned_blocks == sizes[0] + sizes[2]
    assert sum(sizes) == hm.plan.num_dense_blocks
    x = jax.random.normal(jax.random.PRNGKey(19), (1 << 12, 4), jnp.float32)
    with jax.default_matmul_precision("highest"):
        z = np.asarray(make_apply(hm)(x), np.float64)
    p, x64 = np.asarray(pts, np.float64), np.asarray(x, np.float64)
    k64 = _matern64(p, p)
    err = np.linalg.norm(z - k64 @ x64) / np.linalg.norm(k64 @ np.abs(x64))
    assert err < 1.5e-7, err


def test_gaussian_launches_the_whole_list_in_order(monkeypatch):
    """The Gaussian has no branch point: the near field is one group, the
    plan's list in order, in ``launch_batches``' slices, with no range."""
    pts = jnp.asarray(halton(1 << 12, 2))
    hm, report = build_hmatrix_device_report(pts, k=16, c_leaf=128)
    assert report.near_field_pruned_blocks == 0
    assert hmatrix.near_field_groups(hm.plan, hm.kernel) == [(slice(None),
                                                              None)]
    monkeypatch.setattr(hmatrix, "LAUNCH_BYTES", 128 * 8 * 4 * 40)
    seen = []
    real = hmatrix._dense_batch_apply

    def record(*args):
        seen.append((np.asarray(args[6]).copy(), args[8]))
        return real(*args)
    monkeypatch.setattr(hmatrix, "_dense_batch_apply", record)
    make_apply(hm)(jnp.ones((1 << 12, 8), jnp.float32))
    parts = hmatrix.launch_batches(hm.plan.num_dense_blocks, 128 * 8 * 4)
    assert len(parts) > 1 and len(seen) == len(parts)
    for (blocks, r_range), part in zip(seen, parts):
        np.testing.assert_array_equal(blocks, hm.plan.dense_blocks[part])
        assert r_range is None


@pytest.mark.parametrize("kernel", ["gaussian", "matern"])
def test_ranges_leave_the_gaussian_program_alone(kernel):
    """With its bounds or without them, the Gaussian apply lowers to the
    same program; the Matérn's differs (it drops a branch)."""
    pts = jnp.asarray(halton(1 << 12, 3))
    hm = build_hmatrix(pts, kernel=kernel, c_leaf=256, precompute=True)
    blind = dataclasses.replace(
        hm, plan=dataclasses.replace(hm.plan, dense_range=None))
    x = jnp.ones((1 << 12, 4), jnp.float32)
    same = (make_apply(hm).lower(x).as_text()
            == make_apply(blind).lower(x).as_text())
    assert same == (kernel == "gaussian")


def test_unit_cube_design_prunes_every_block():
    """Halton points in [0,1]^3 lie within sqrt(3) < 2 of each other: every
    near-field block is in the small-only group, the whole list in order,
    and the product is the two-branch product bit for bit."""
    pts = jnp.asarray(halton(1 << 14, 3))
    hm, report = build_hmatrix_device_report(pts, kernel="matern", k=16,
                                             c_leaf=256, precompute=True)
    [(sel, (lo, hi))] = hmatrix.near_field_groups(hm.plan, hm.kernel)
    assert sel == slice(None) and hi <= math.sqrt(3) < K1_BRANCH
    assert report.near_field_pruned_blocks == report.num_dense_blocks > 0
    blind = dataclasses.replace(
        hm, plan=dataclasses.replace(hm.plan, dense_range=None))
    x = jax.random.normal(jax.random.PRNGKey(20), (1 << 14, 4), jnp.float32)
    with jax.default_matmul_precision("highest"):
        z = np.asarray(make_apply(hm)(x))
        z_both = np.asarray(make_apply(blind)(x))
    assert np.array_equal(z, z_both)
