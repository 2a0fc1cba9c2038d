"""The paper's 3-D Matérn problem (§6.2) through the normal path — the
device build in P mode and ``make_apply`` — against a float64 dense
product with scipy's Bessel K_1, and the Matérn kernel pinned bit for bit
to the formula it had before the library kept a single K_1."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.special

from repro.core import (build_device, build_hmatrix_device_report, halton,
                        hmatrix, make_apply)
from repro.core.geometry import _sqdist, matern_kernel
from repro.kernels._phi import phi_from_sqdist

N, C_LEAF, K, ETA = 2 ** 13, 256, 16, 1.5


def _matern64(y, yp):
    """phi(y, y') in float64 with scipy's K_1 (beta = d/2 + 1)."""
    d = y.shape[1]
    beta = d / 2 + 1
    r = np.sqrt(((y[:, None, :] - yp[None, :, :]) ** 2).sum(-1))
    with np.errstate(invalid="ignore"):
        val = np.where(r > 0, r * scipy.special.k1(r), 1.0)
    return val / (2 ** (beta - 1) * math.gamma(beta))


def _dense64(pts, x, block=1024):
    p = np.asarray(pts, np.float64)
    x = np.asarray(x, np.float64)
    return np.concatenate([_matern64(p[i:i + block], p) @ x
                           for i in range(0, len(p), block)])


@pytest.fixture(scope="module")
def built():
    pts = jnp.asarray(halton(N, 3))
    hm, report = build_hmatrix_device_report(pts, kernel="matern", k=K,
                                             c_leaf=C_LEAF, eta=ETA,
                                             precompute=True)
    return pts, hm, report


@pytest.fixture(scope="module")
def inputs(built):
    """An (N, 8) seeded panel and one vector, each with its float64
    products K X and K |X|."""
    pts = built[0]
    panel = jax.random.normal(jax.random.PRNGKey(17), (N, 8), jnp.float32)
    vector = jax.random.normal(jax.random.PRNGKey(18), (N,), jnp.float32)
    x = np.concatenate([np.asarray(panel), np.asarray(vector)[:, None]], 1)
    want = _dense64(pts, np.concatenate([x, np.abs(x)], 1))
    return {"panel": (panel, want[:, :8], want[:, 9:17]),
            "vector": (vector, want[:, 8], want[:, 17])}


def test_plan_is_three_dimensional(built):
    _, hm, report = built
    assert hm.tree.points.shape == (N, 3)
    assert hm.kernel_name == "matern"
    assert report.num_dense_blocks > 0 and len(hm.plan.aca_levels) >= 2
    assert report.near_field_entries == report.num_dense_blocks * C_LEAF ** 2


@pytest.mark.parametrize("which", ["panel", "vector"])
def test_apply_matches_float64_reference(built, inputs, which):
    """The error ||Z - K X|| / ||K |X| ||: a float32 product of a positive
    kernel errs by some ulps of K |X| entry by entry, and the H-matrix's
    rank-16 ACA by a share of the entries it approximates, so the measure
    is steady from input to input (||K x|| alone follows x's component
    along K's first eigenvector: one vector's ||Z - K x|| / ||K x|| read
    1.1e-6 to 1.4e-5 over 8 seeds).  It reads 3.3e-8 to 4.5e-8 for 8
    vectors and the panel; the bound, 1.5e-7, leaves three times that
    and lies under what the same inputs read at rank 8 (5.4e-7 to
    8.2e-7), so a lost level or a wrong block does not pass."""
    x, want, scale = inputs[which]
    with jax.default_matmul_precision("highest"):
        z = np.asarray(make_apply(built[1])(x), np.float64)
    err = np.linalg.norm(z - want) / np.linalg.norm(scale)
    assert err < 1.5e-7, err


def test_launch_batches_at_the_benchmark_sizes():
    """At 2^19 points and R = 64 every launch of the 2-D model problem
    stays whole; the 3-D Matérn problem's near field, L8 and L7 split."""
    def count(blocks, m, cols=64):
        return len(hmatrix.launch_batches(blocks, m * cols * 4))
    # paper2d: the near field and its largest level groups, R 64 and 1
    assert count(3974, 2048) == count(4610, 2048) == count(850, 8192) == 1
    assert count(3974, 2048, 1) == 1
    # paper3d_matern: near field (18106, 2048), L8 (13398, 2048),
    # L7 (3836, 4096), L6 (800, 8192)
    assert [count(18106, 2048), count(13398, 2048), count(3836, 4096),
            count(800, 8192)] == [4, 3, 2, 1]
    parts = hmatrix.launch_batches(18106, 2048 * 64 * 4)
    assert [p.start for p in parts] == [0, 4527, 9054, 13581]
    sizes = [len(range(18106)[p]) for p in parts]
    assert sizes == [4527, 4527, 4527, 4525]
    assert max(sizes) * 2048 * 64 * 4 <= hmatrix.LAUNCH_BYTES


@pytest.mark.parametrize("precompute", [True, False], ids=["P", "NP"])
def test_batched_launches_match_whole_ones(built, inputs, monkeypatch,
                                           precompute):
    """Under a small budget every launch splits: the factors are the same
    bit for bit (each block's ACA is its own), and the product differs only
    by the order of the batches' sums."""
    pts, hm, _ = built
    x = inputs["panel"][0]
    whole, _ = build_hmatrix_device_report(pts, kernel="matern", k=K,
                                           c_leaf=C_LEAF, eta=ETA,
                                           precompute=precompute)
    with jax.default_matmul_precision("highest"):
        z_whole = np.asarray(make_apply(whole)(x))
    monkeypatch.setattr(hmatrix, "LAUNCH_BYTES", 300_000)
    assert len(hmatrix.launch_batches(hm.plan.num_dense_blocks,
                                      C_LEAF * 8 * 4)) > 1
    # the apply splits its largest level group too; the build every one
    top = max(hm.plan.aca_levels, key=lambda lv: hm.plan.aca_levels[lv].size)
    assert len(hmatrix.launch_batches(hm.plan.aca_levels[top].shape[0],
                                      (N >> top) * 8 * 4)) > 1
    assert all(len(hmatrix.launch_batches(
        b.shape[0], (N >> lv) * build_device.ACA_PANEL_COLS * 4)) > 1
        for lv, b in hm.plan.aca_levels.items())
    split, report = build_hmatrix_device_report(pts, kernel="matern", k=K,
                                                c_leaf=C_LEAF, eta=ETA,
                                                precompute=precompute)
    if precompute:
        assert report.launches == 1 + sum(
            len(hmatrix.launch_batches(
                b.shape[0], (N >> lv) * build_device.ACA_PANEL_COLS * 4))
            for lv, b in hm.plan.aca_levels.items())
    if precompute:
        for level in hm.plan.aca_levels:
            for a, b in zip(split.factors[level], whole.factors[level]):
                assert np.array_equal(np.asarray(a), np.asarray(b)), level
    with jax.default_matmul_precision("highest"):
        z = np.asarray(make_apply(split)(x))
    assert np.allclose(z, z_whole, rtol=1e-5, atol=1e-6 * np.abs(z).max())


def test_build_launches_larger_level_groups_first(built):
    _, hm, _ = built
    rows = {lv: b.shape[0] * (N >> lv) for lv, b in hm.plan.aca_levels.items()}
    order = sorted(rows, key=rows.get, reverse=True)
    seen = []
    real = build_device._contained_stage

    def record(name, fn, *args):
        seen.append(int(name.rsplit(":", 1)[1]))
        return real(name, fn, *args)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(build_device, "_contained_stage", record)
        got = build_device.compute_factors_device(hm.tree, hm.plan, "matern",
                                                  K)
    assert [lv for i, lv in enumerate(seen) if lv not in seen[:i]] == order
    assert list(got) == list(hm.plan.aca_levels)


def _old_bessel_k1(x):
    """K_1 as ``core/geometry.py`` had it (Abramowitz & Stegun 9.8.7/8)."""
    x = jnp.asarray(x)
    small = x <= 2.0
    xs = jnp.where(small, x, 2.0)
    xl = jnp.where(small, 2.0, x)
    t = (xs / 3.75) ** 2
    i1 = xs * (0.5 + t * (0.87890594 + t * (0.51498869 + t * (0.15084934
         + t * (0.02658733 + t * (0.00301532 + t * 0.00032411))))))
    u = (xs / 2.0) ** 2
    p = 1.0 + u * (0.15443144 + u * (-0.67278579 + u * (-0.18156897
        + u * (-0.01919402 + u * (-0.00110404 + u * (-0.00004686))))))
    k1_small = jnp.log(xs / 2.0) * i1 + p / xs
    w = 2.0 / xl
    q = 1.25331414 + w * (0.23498619 + w * (-0.03655620 + w * (0.01504268
        + w * (-0.00780353 + w * (0.00325614 + w * (-0.00068245))))))
    k1_large = jnp.exp(-xl) / jnp.sqrt(xl) * q
    return jnp.where(small, k1_small, k1_large)


def _old_matern(y, yp):
    d = y.shape[-1]
    beta = d / 2.0 + 1.0
    norm = (2.0 ** (beta - 1.0)) * math.gamma(beta)
    r = jnp.sqrt(_sqdist(y, yp))
    val = jnp.where(r > 1e-8, r * _old_bessel_k1(jnp.maximum(r, 1e-30)), 1.0)
    return val / norm


@pytest.mark.parametrize("d", [2, 3])
def test_single_k1_keeps_the_kernel_values(d):
    """``matern_kernel`` and the Pallas kernels' ``phi_from_sqdist`` give
    the pre-change values bit for bit, r = 0 (repeated points) included."""
    y = jnp.asarray(np.random.default_rng(d).random((200, d)) * 4.0,
                    jnp.float32)
    y = jnp.concatenate([y, y[:7]])
    want = np.asarray(_old_matern(y, y))
    assert np.array_equal(np.asarray(matern_kernel(y, y)), want)
    got = np.asarray(phi_from_sqdist(_sqdist(y, y), "matern", d))
    assert np.array_equal(got, want)
    phi0 = np.float32(1.0) / np.float32(2 ** (d / 2) * math.gamma(d / 2 + 1))
    assert np.all(np.diag(want) == phi0)
    assert np.all(np.diag(want[:7, 200:]) == phi0)
