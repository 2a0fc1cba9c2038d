"""Differential tests: on-device construction vs the host oracle.

``build_hmatrix_device`` must be a drop-in for ``build_hmatrix``: same
Morton permutation, same per-level bounding boxes, the same plan arrays
(admissible sets per level + dense-leaf set), bit-identical ACA factors
(same ``batched_aca`` executable) and bit-identical apply/solve results.
The geometry edge cases — N not a power of two, duplicate points,
collinear points, scaled/translated domains, ``c_leaf >= N`` — run
through ONE shared case table so both builders face identical inputs,
and the structural invariants (exact tiling, admissibility condition)
are parametrized over host and device builders alike.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_hmatrix, build_hmatrix_device,
                        build_hmatrix_device_report, compute_factors,
                        compute_factors_device, eval_dense_leaves, halton,
                        make_apply)
from repro.core.geometry import get_kernel
from repro.core.morton import morton_encode
from repro.kernels.batched_aca.ops import batched_aca_level
from repro.kernels.batched_aca.ref import batched_aca_level_ref
from repro.solve import make_solver


@pytest.fixture()
def rng():
    # shadow the session-scoped stream: this suite must not shift the draw
    # order that other test modules' tolerance-tuned assertions depend on
    return np.random.RandomState(7)


def _dup_points(n, d):
    pts = np.array(halton(n, d), dtype=np.float32)     # writable copy
    pts[n // 3: n // 3 + 40] = pts[7]                  # duplicate cluster
    pts[::11] = pts[3]                                 # scattered repeats
    return pts


def _collinear(n):
    t = np.linspace(0.0, 5.0, n, dtype=np.float32)
    return np.stack([t, np.full(n, 2.5, np.float32)], axis=1)


# name -> (points factory, c_leaf, eta)
CASES = {
    "halton2d": (lambda: np.asarray(halton(1500, 2)) * 32.0, 128, 1.5),
    "nonpow2-3d": (lambda: np.asarray(halton(777, 3)), 64, 2.0),
    "duplicates": (lambda: _dup_points(900, 2), 64, 1.0),
    "collinear": (lambda: _collinear(640), 64, 1.5),
    "scaled-translated": (lambda: np.asarray(halton(1000, 2)) * 1e4 - 7e3,
                          128, 1.5),
    "single-leaf": (lambda: np.asarray(halton(300, 2)), 512, 1.5),
}


def _build_pair(case, **kw):
    factory, c_leaf, eta = CASES[case]
    pts = factory()
    return (build_hmatrix(pts, c_leaf=c_leaf, eta=eta, **kw),
            build_hmatrix_device(pts, c_leaf=c_leaf, eta=eta, **kw))


def _assert_plans_equal(pa, pb):
    assert (pa.c_leaf, pa.n_pad, pa.n_levels, pa.eta) == \
           (pb.c_leaf, pb.n_pad, pb.n_levels, pb.eta)
    assert sorted(pa.aca_levels) == sorted(pb.aca_levels)
    for lvl, blocks in pa.aca_levels.items():
        np.testing.assert_array_equal(blocks, pb.aca_levels[lvl])
    np.testing.assert_array_equal(pa.dense_blocks, pb.dense_blocks)


# ---------------------------------------------------------------------------
# structural equality: plan, permutation, boxes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_plan_matches_host_exactly(case):
    host, dev = _build_pair(case)
    np.testing.assert_array_equal(np.asarray(dev.tree.perm),
                                  np.asarray(host.tree.perm))
    np.testing.assert_array_equal(np.asarray(dev.tree.iperm),
                                  np.asarray(host.tree.iperm))
    np.testing.assert_array_equal(np.asarray(dev.tree.points),
                                  np.asarray(host.tree.points))
    for lvl in range(host.tree.n_levels + 1):
        np.testing.assert_array_equal(np.asarray(dev.tree.bb_min[lvl]),
                                      np.asarray(host.tree.bb_min[lvl]))
        np.testing.assert_array_equal(np.asarray(dev.tree.bb_max[lvl]),
                                      np.asarray(host.tree.bb_max[lvl]))
    _assert_plans_equal(host.plan, dev.plan)


@pytest.mark.parametrize("case,ties", [("halton2d", False),
                                       ("duplicates", True)],
                         ids=["rank-branch", "tie-branch"])
def test_device_inverse_permutation_on_both_sort_branches(case, ties):
    """The plan's sort recovers the permutation by rank unless the high
    Morton halves tie (duplicated points), when it falls back to the full
    sort; the inverse permutation must be exact on both branches."""
    factory, c_leaf, eta = CASES[case]
    pts = jnp.asarray(factory())
    lo, hi = pts.min(axis=0), pts.max(axis=0)
    code_hi, _ = morton_encode((pts - lo) / jnp.maximum(hi - lo, 1e-30))
    n = pts.shape[0]
    assert (np.unique(np.asarray(code_hi)).size < n) == ties
    hm = build_hmatrix_device(pts, c_leaf=c_leaf, eta=eta)
    perm, iperm = np.asarray(hm.tree.perm), np.asarray(hm.tree.iperm)
    assert iperm.dtype == np.int32
    np.testing.assert_array_equal(iperm, np.argsort(perm))
    np.testing.assert_array_equal(perm[iperm], np.arange(n))


def test_single_leaf_degenerates_to_one_dense_block():
    host, dev = _build_pair("single-leaf")
    for hm in (host, dev):
        assert hm.plan.n_levels == 0
        assert hm.plan.aca_levels == {}
        np.testing.assert_array_equal(hm.plan.dense_blocks,
                                      np.zeros((1, 2), np.int32))


# ---------------------------------------------------------------------------
# shared structural-invariant suite over BOTH builders
# ---------------------------------------------------------------------------

BUILDERS = {"host": build_hmatrix, "device": build_hmatrix_device}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_partition_tiles_exactly_both_builders(builder, case):
    factory, c_leaf, eta = CASES[case]
    hm = BUILDERS[builder](factory(), c_leaf=c_leaf, eta=eta)
    assert hm.plan.coverage_check()


@pytest.mark.parametrize("case", ["duplicates", "collinear"])
@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_degenerate_geometry_sane(builder, case):
    """Duplicate / collinear inputs must still produce a valid partition
    with a lossless permutation (every input point appears once)."""
    factory, c_leaf, eta = CASES[case]
    pts = factory()
    hm = BUILDERS[builder](pts, c_leaf=c_leaf, eta=eta)
    perm = np.asarray(hm.tree.perm)
    assert sorted(perm.tolist()) == list(range(pts.shape[0]))
    np.testing.assert_array_equal(
        np.asarray(hm.tree.points[: pts.shape[0]]), pts[perm])


# ---------------------------------------------------------------------------
# factor assembly: device level-group launches vs the host driver
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["halton2d", "duplicates", "single-leaf"])
def test_device_factors_bit_identical(case):
    host, dev = _build_pair(case, k=10, precompute=True)
    assert sorted(host.factors) == sorted(dev.factors)
    for lvl in host.factors:
        for a, b in zip(host.factors[lvl], dev.factors[lvl]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compute_factors_device_matches_host_driver():
    """The standalone device driver (registered-name path) reproduces
    ``compute_factors`` bitwise on a host-built H-matrix."""
    factory, c_leaf, eta = CASES["halton2d"]
    hm = build_hmatrix(factory(), c_leaf=c_leaf, eta=eta, k=12)
    want = compute_factors(hm.tree, hm.plan, hm.kernel, 12)
    got = compute_factors_device(hm.tree, hm.plan, "gaussian", 12)
    assert sorted(want) == sorted(got)
    for lvl in want:
        for a, b in zip(want[lvl], got[lvl]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_aca_level_matches_ref_oracle(rng):
    """Construction entry point vs its ref.py oracle.  Pallas and ref ACA
    may pick different pivots on ties, so compare each reconstruction
    against the true kernel block (same contract as the other kernels)."""
    hm = build_hmatrix(np.asarray(halton(1024, 2)), c_leaf=128, eta=1.0)
    k = 12
    for lvl, blocks in hm.plan.aca_levels.items():
        rows, cols = jnp.asarray(blocks[:, 0]), jnp.asarray(blocks[:, 1])
        u, v = batched_aca_level(hm.tree.points, rows, cols, lvl,
                                 "gaussian", k)
        ur, vr = batched_aca_level_ref(hm.tree.points, rows, cols, lvl,
                                       "gaussian", k)
        m = hm.tree.n_pad >> lvl
        pts = hm.tree.points.reshape(1 << lvl, m, -1)
        a = get_kernel("gaussian")(pts[rows], pts[cols])
        err = float(jnp.max(jnp.abs(a - jnp.einsum("bmk,bnk->bmn", u, v))))
        err_ref = float(jnp.max(jnp.abs(a - jnp.einsum("bmk,bnk->bmn",
                                                       ur, vr))))
        assert err < max(2.0 * err_ref, 1e-4), (lvl, err, err_ref)


def test_dense_leaves_match_eager_oracle():
    """The one-launch dense-leaf batch equals per-block eager evaluation."""
    factory, c_leaf, eta = CASES["duplicates"]
    hm = build_hmatrix_device(factory(), c_leaf=c_leaf, eta=eta)
    batch = np.asarray(eval_dense_leaves(hm))
    assert batch.shape == (hm.plan.num_dense_blocks, c_leaf, c_leaf)
    pts = np.asarray(hm.tree.points)
    for i, (r, c) in enumerate(np.asarray(hm.plan.dense_blocks)[:8]):
        rp = jnp.asarray(pts[r * c_leaf:(r + 1) * c_leaf])
        cp = jnp.asarray(pts[c * c_leaf:(c + 1) * c_leaf])
        np.testing.assert_allclose(batch[i], np.asarray(hm.kernel(rp, cp)),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end: the device-built H-matrix serves bit-identically
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["halton2d", "nonpow2-3d", "duplicates"])
def test_apply_bit_identical(case, rng):
    host, dev = _build_pair(case)
    n = host.tree.n
    x = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    zh = make_apply(host)(x)
    zd = make_apply(dev)(x)
    np.testing.assert_array_equal(np.asarray(zh), np.asarray(zd))


def test_apply_bit_identical_precomputed(rng):
    host, dev = _build_pair("halton2d", k=8, precompute=True)
    x = jnp.asarray(rng.randn(host.tree.n).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(make_apply(host)(x)),
                                  np.asarray(make_apply(dev)(x)))


def test_solve_bit_identical(rng):
    factory, c_leaf, eta = CASES["nonpow2-3d"]
    pts = factory()
    n = pts.shape[0]
    F = jnp.asarray(rng.randn(n, 2).astype(np.float32))
    host = build_hmatrix(pts, c_leaf=c_leaf, eta=eta, k=12)
    dev = build_hmatrix_device(pts, c_leaf=c_leaf, eta=eta, k=12)
    ch, ih = make_solver(host, 0.5, tol=1e-5, max_iter=200)(F)
    cd, idv = make_solver(dev, 0.5, tol=1e-5, max_iter=200)(F)
    assert ih.converged and idv.converged
    assert int(ih.iterations) == int(idv.iterations)
    np.testing.assert_array_equal(np.asarray(ch), np.asarray(cd))


# ---------------------------------------------------------------------------
# the instrumented report
# ---------------------------------------------------------------------------


def test_build_report_counts_and_timings():
    factory, c_leaf, eta = CASES["halton2d"]
    hm, rep = build_hmatrix_device_report(factory(), c_leaf=c_leaf, eta=eta,
                                          k=8, precompute=True)
    assert rep.n == 1500 and rep.n_pad == hm.plan.n_pad
    assert rep.num_aca_blocks == hm.plan.num_aca_blocks
    assert rep.num_dense_blocks == hm.plan.num_dense_blocks
    assert rep.launches == 1 + len(hm.plan.aca_levels)
    assert rep.total_s >= rep.plan_s > 0 and rep.factors_s > 0
    assert rep.retries == 0 and rep.fallback_launches == 0
    assert rep.faults_injected == {}


def test_build_rejects_non_pow2_c_leaf():
    with pytest.raises(ValueError, match="power of two"):
        build_hmatrix_device(np.asarray(halton(256, 2)), c_leaf=100)


def test_custom_callable_kernel_matches_host(rng):
    """Unregistered kernels route through the shared batched-ACA closure
    and still match the host driver bitwise."""
    kfn = get_kernel("gaussian")
    pts = np.asarray(halton(800, 2))
    host = build_hmatrix(pts, kernel=kfn, c_leaf=64, eta=1.0, k=8,
                         precompute=True)
    dev = build_hmatrix_device(pts, kernel=kfn, c_leaf=64, eta=1.0, k=8,
                               precompute=True)
    _assert_plans_equal(host.plan, dev.plan)
    for lvl in host.factors:
        for a, b in zip(host.factors[lvl], dev.factors[lvl]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
