"""Cluster tree invariants C1-C4 (paper §2.1) + bounding boxes (§5.3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.clustering import build_cluster_tree, next_pow2, permute_from_tree, permute_to_tree
from repro.core.geometry import halton


def test_next_pow2():
    assert [next_pow2(i) for i in (1, 2, 3, 5, 8, 1000)] == [1, 2, 4, 8, 8, 1024]


@settings(max_examples=15, deadline=None)
@given(st.integers(20, 700), st.sampled_from([16, 32, 64]), st.integers(2, 3))
def test_tree_invariants(n, c_leaf, d):
    pts = halton(n, d)
    tree = build_cluster_tree(pts, c_leaf=c_leaf)
    # C2: root covers I_pad; C4: clusters split into equal halves
    assert tree.n_pad == max(next_pow2(n), c_leaf)
    assert tree.cluster_size(0) == tree.n_pad
    for lvl in range(tree.n_levels + 1):
        m = tree.cluster_size(lvl)
        assert m * tree.num_clusters(lvl) == tree.n_pad   # disjoint partition
        assert m >= c_leaf                                 # C3 at leaves: == c_leaf
    assert tree.cluster_size(tree.n_levels) == c_leaf


def test_bounding_boxes_match_bruteforce(rng):
    pts = jnp.asarray(rng.rand(500, 2).astype(np.float32))
    tree = build_cluster_tree(pts, c_leaf=32)
    sorted_pts = np.asarray(tree.points)
    for lvl in (0, 1, tree.n_levels):
        m = tree.cluster_size(lvl)
        for i in (0, tree.num_clusters(lvl) - 1):
            seg = sorted_pts[i * m:(i + 1) * m]
            np.testing.assert_allclose(np.asarray(tree.bb_min[lvl][i]), seg.min(0), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(tree.bb_max[lvl][i]), seg.max(0), rtol=1e-6)


@pytest.mark.parametrize("n,r", [(300, None), (300, 5), (512, 3)],
                         ids=["padded-vector", "padded-panel",
                              "unpadded-panel"])
def test_permutation_roundtrip(rng, n, r):
    pts = jnp.asarray(rng.rand(n, 3).astype(np.float32))
    tree = build_cluster_tree(pts, c_leaf=64)
    assert (tree.n_pad > n) == (n == 300)
    perm, iperm = np.asarray(tree.perm), np.asarray(tree.iperm)
    assert iperm.dtype == np.int32
    np.testing.assert_array_equal(perm[iperm], np.arange(n))
    np.testing.assert_array_equal(iperm[perm], np.arange(n))
    shape = (n,) if r is None else (n, r)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    xp = permute_to_tree(tree, x)
    assert xp.shape == (tree.n_pad,) + shape[1:]
    # a nonzero padded tail must not leak into the original order
    xp = xp.at[n:].set(1e6)
    x2 = permute_from_tree(tree, xp)
    np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
