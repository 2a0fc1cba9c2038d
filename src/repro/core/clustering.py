"""Cardinality-based clustering (CBC) on the Morton-sorted point array.

Paper §2.1 + §4.4: after Z-order sorting, splitting a cluster into two
spatially distinct halves is just splitting a contiguous index range in the
middle.  We pad N to a power of two (duplicating the last sorted point; the
padded tail is masked out of every matvec) so the cluster tree is *perfectly
balanced*: at level ``l`` there are exactly ``2^l`` clusters, each the
contiguous range ``[i * m, (i+1) * m)`` with ``m = N_pad / 2^l``.

TPU adaptation (DESIGN.md §3.2): the balanced tree turns the paper's
``reduce_by_key`` bounding-box batching (Alg. 7) into a dense reshape-reduce,
and the node→lookup-table map (Alg. 8) into the identity (cluster id).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .morton import morton_sort


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass(frozen=True)
class ClusterTree:
    """Implicit balanced cluster tree over the Morton-sorted points.

    Attributes
    ----------
    points:   (N_pad, d) Morton-sorted (and padded) coordinates.
    perm:     (N,) permutation from original ordering to sorted ordering
              (``sorted[i] = original[perm[i]]``).
    iperm:    (N,) inverse of ``perm`` (``iperm[perm[i]] = i``): ``iperm[j]``
              is the tree position of original point ``j``, so moving a
              tree-ordered vector back to the original order is a gather.
    n:        true number of points (<= N_pad).
    n_pad:    padded size (power of two).
    c_leaf:   leaf cluster size (power of two).
    n_levels: number of levels L such that clusters at level L have size c_leaf.
    bb_min, bb_max: tuples over levels; level l entries have shape (2^l, d) —
              the paper's bb_lookup_table, one per level.
    """

    points: jnp.ndarray
    perm: jnp.ndarray
    iperm: jnp.ndarray
    n: int
    n_pad: int
    c_leaf: int
    n_levels: int
    bb_min: tuple
    bb_max: tuple

    def cluster_size(self, level: int) -> int:
        return self.n_pad >> level

    def num_clusters(self, level: int) -> int:
        return 1 << level

    def cluster_range(self, level: int, idx: int) -> tuple[int, int]:
        m = self.cluster_size(level)
        return idx * m, (idx + 1) * m


def _level_bounding_boxes(points: jnp.ndarray, n_levels: int):
    """All-level bounding boxes, bottom-up (O(N) total work).

    Level L (leaves) via reshape-reduce; parents by combining child pairs.
    """
    n_pad, d = points.shape
    mins, maxs = [], []
    m_leaf = n_pad >> n_levels
    cur_min = points.reshape(1 << n_levels, m_leaf, d).min(axis=1)
    cur_max = points.reshape(1 << n_levels, m_leaf, d).max(axis=1)
    mins.append(cur_min)
    maxs.append(cur_max)
    for _ in range(n_levels):
        cur_min = cur_min.reshape(-1, 2, d).min(axis=1)
        cur_max = cur_max.reshape(-1, 2, d).max(axis=1)
        mins.append(cur_min)
        maxs.append(cur_max)
    mins.reverse()
    maxs.reverse()
    return tuple(mins), tuple(maxs)


def build_cluster_tree(coords: jnp.ndarray, c_leaf: int = 256) -> ClusterTree:
    """Morton-sort, pad, and build the implicit balanced cluster tree.

    Properties C1-C4 of the paper hold by construction: every cluster is a
    non-empty contiguous range (C1), level 0 is I (C2), leaves have exactly
    ``c_leaf`` members (C3, bound attained), and every interior node splits
    into exactly two equal halves (C4).
    """
    n, d = coords.shape
    if c_leaf & (c_leaf - 1):
        raise ValueError("c_leaf must be a power of two")
    # Morton quantisation assumes [0,1]^d (out-of-range coords clip to the
    # same code, degenerating the sort): encode on the normalised unit box,
    # keep the true coordinates for all geometry.
    lo, hi = coords.min(axis=0), coords.max(axis=0)
    unit = (coords - lo) / jnp.maximum(hi - lo, 1e-30)
    _, perm = morton_sort(unit)
    sorted_pts = coords[perm]
    n_pad = max(next_pow2(n), c_leaf)
    if n_pad > n:
        pad = jnp.broadcast_to(sorted_pts[-1], (n_pad - n, d))
        sorted_pts = jnp.concatenate([sorted_pts, pad], axis=0)
    n_levels = int(np.log2(n_pad // c_leaf))
    bb_min, bb_max = _level_bounding_boxes(sorted_pts, n_levels)
    iperm = jnp.argsort(perm).astype(jnp.int32)
    return ClusterTree(points=sorted_pts, perm=perm, iperm=iperm, n=n,
                       n_pad=n_pad, c_leaf=c_leaf, n_levels=n_levels,
                       bb_min=bb_min, bb_max=bb_max)


def permute_to_tree(tree: ClusterTree, x: jnp.ndarray) -> jnp.ndarray:
    """Vector in original ordering -> padded tree (Morton) ordering."""
    xp = x[tree.perm]
    if tree.n_pad > tree.n:
        xp = jnp.concatenate([xp, jnp.zeros((tree.n_pad - tree.n,) + x.shape[1:], x.dtype)])
    return xp


def permute_from_tree(tree: ClusterTree, z_pad: jnp.ndarray) -> jnp.ndarray:
    """Padded tree-ordered vector -> original ordering (drops the pad).

    A row gather by the inverse permutation; every ``iperm`` value is below
    ``n``, so the padded tail is never read."""
    return z_pad[tree.iperm]
