"""Block cluster tree construction by level-wise parallel traversal.

This is the paper's Algorithm 1 (block cluster tree) executed with the
many-core tree-traversal pattern of Algorithm 4: the frontier of one level is
held in flat arrays; a *count* kernel decides children per node (0 for leaves,
4 otherwise), an *exclusive scan* computes output offsets, and a *compact*
step materialises the next frontier.  Leaf nodes are emitted into work queues
(paper §4.3/§5.4) — here deterministic compactions instead of atomic queues
(DESIGN.md §3.1).

Because the cluster tree is perfectly balanced (clustering.py), a node is
just an integer pair ``(row_cluster, col_cluster)`` at a level — the paper's
``work_item`` index bounds are recovered as ``[i*m, (i+1)*m)``.

Everything is expressed with vectorised jnp ops; sizes are data-dependent per
level so this runs eagerly (construction is metadata-only and tiny next to
the numerics, cf. paper Fig 12: traversal is a small fraction of total time).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from .admissibility import admissible
from .clustering import ClusterTree


@dataclass(frozen=True)
class HMatrixPlan:
    """The static "work queues": where each leaf block of the partition goes.

    aca_levels:  dict level -> (n_l, 2) int32 array of (row, col) cluster ids
                 of admissible blocks at that level (approximated at rank k).
    dense_blocks: (n_dense, 2) int32 array at leaf level (direct evaluation).
    c_leaf, n_pad, n_levels: geometry of the partition.
    dense_range: (n_dense, 2) float64 bounds [r_lo, r_hi] on the distance
                 between any point of a dense block's row leaf and any of
                 its column leaf (:func:`near_field_ranges`), or None where
                 they are not known (every branch of the kernel is taken).
    """

    aca_levels: dict
    dense_blocks: np.ndarray
    c_leaf: int
    n_pad: int
    n_levels: int
    eta: float
    dense_range: np.ndarray | None = None

    @property
    def num_aca_blocks(self) -> int:
        return int(sum(v.shape[0] for v in self.aca_levels.values()))

    @property
    def num_dense_blocks(self) -> int:
        return int(self.dense_blocks.shape[0])

    def coverage_check(self) -> bool:
        """True iff the leaf blocks tile I_pad x I_pad exactly once.

        O(num_blocks) interval arithmetic — used by property tests.
        """
        total = 0
        for lvl, blocks in self.aca_levels.items():
            m = self.n_pad >> lvl
            total += int(blocks.shape[0]) * m * m
        total += self.num_dense_blocks * self.c_leaf * self.c_leaf
        return total == self.n_pad * self.n_pad


def _admissible_np(a_min, a_max, b_min, b_max, eta):
    d_a = np.sqrt(((a_max - a_min) ** 2).sum(-1))
    d_b = np.sqrt(((b_max - b_min) ** 2).sum(-1))
    gap_ab = np.maximum(0.0, a_min - b_max)
    gap_ba = np.maximum(0.0, b_min - a_max)
    dist = np.sqrt((gap_ab ** 2 + gap_ba ** 2).sum(-1))
    # eta stays f32 like the jnp path's weak-typed scalar: a python-float
    # eta would promote the comparison to f64 under pre-NEP50 NumPy and
    # could flip borderline blocks vs the device traversal
    return np.minimum(d_a, d_b) <= np.float32(eta) * dist


def near_field_ranges(leaf_min, leaf_max, blocks) -> np.ndarray:
    """(n_blocks, 2) float64 ``[r_lo, r_hi]`` for leaf blocks ``(i, j)``:
    the gap between the two leaf boxes (``dist`` of the admissibility test)
    and the length of the box spanned by the farthest corners,
    sqrt(sum_d max(max_i - min_j, max_j - min_i)^2)."""
    lo = np.asarray(leaf_min, np.float64)
    hi = np.asarray(leaf_max, np.float64)
    r, c = np.asarray(blocks[:, 0]), np.asarray(blocks[:, 1])
    gap = (np.maximum(0.0, lo[r] - hi[c]) ** 2
           + np.maximum(0.0, lo[c] - hi[r]) ** 2).sum(-1)
    span = (np.maximum(hi[r] - lo[c], hi[c] - lo[r]) ** 2).sum(-1)
    return np.stack([np.sqrt(gap), np.sqrt(span)], axis=1)


def build_block_tree(tree: ClusterTree, eta: float = 1.5,
                     backend: str = "np") -> HMatrixPlan:
    """Level-wise traversal: count -> exclusive scan -> compact per level.

    ``backend="np"``: the (tiny) per-level metadata math runs as vectorised
    NumPy on host — the pattern is identical but avoids per-level device
    round-trips (this container's CPU "device" gains nothing from them).
    ``backend="jnp"``: same steps as device ops — the accelerator-resident
    variant, kept for parity tests and on-device deployment.
    """
    use_np = backend == "np"
    bb_min = [np.asarray(b) for b in tree.bb_min] if use_np else tree.bb_min
    bb_max = [np.asarray(b) for b in tree.bb_max] if use_np else tree.bb_max
    xp = np if use_np else jnp

    frontier_r = xp.zeros((1,), xp.int32)
    frontier_c = xp.zeros((1,), xp.int32)
    aca_levels: dict[int, np.ndarray] = {}
    dense_blocks = None

    for level in range(tree.n_levels + 1):
        bmn, bmx = bb_min[level], bb_max[level]
        if use_np:
            adm = _admissible_np(bmn[frontier_r], bmx[frontier_r],
                                 bmn[frontier_c], bmx[frontier_c], eta)
        else:
            adm = admissible(bmn[frontier_r], bmx[frontier_r],
                             bmn[frontier_c], bmx[frontier_c], eta)
        is_leaf_level = level == tree.n_levels

        # --- emit admissible blocks at this level into the ACA queue
        adm_idx = xp.nonzero(adm)[0]
        if adm_idx.shape[0] > 0:
            aca_levels[level] = np.stack(
                [np.asarray(frontier_r[adm_idx]), np.asarray(frontier_c[adm_idx])],
                axis=1).astype(np.int32)

        if is_leaf_level:
            dense_idx = xp.nonzero(~adm)[0]
            dense_blocks = np.stack(
                [np.asarray(frontier_r[dense_idx]), np.asarray(frontier_c[dense_idx])],
                axis=1).astype(np.int32)
            break

        # --- count -> scan -> compact (Algorithm 4)
        child_count = xp.where(adm, 0, 4).astype(xp.int32)
        child_offset = xp.cumsum(child_count) - child_count  # exclusive scan
        n_next = int(child_count.sum())
        if n_next == 0:  # whole remaining matrix admissible (cannot happen at level 0)
            dense_blocks = np.zeros((0, 2), np.int32)
            break
        # Each splitting node expands to 4 children: (2r+a, 2c+b).
        split_idx = xp.nonzero(~adm)[0]
        r, c = frontier_r[split_idx], frontier_c[split_idx]
        quad = xp.arange(4, dtype=xp.int32)
        child_r = (2 * r[:, None] + (quad[None, :] // 2)).reshape(-1)
        child_c = (2 * c[:, None] + (quad[None, :] % 2)).reshape(-1)
        frontier_r, frontier_c = child_r, child_c

    if dense_blocks is None:
        dense_blocks = np.zeros((0, 2), np.int32)
    leaf = tree.n_levels
    return HMatrixPlan(aca_levels=aca_levels, dense_blocks=dense_blocks,
                       c_leaf=tree.c_leaf, n_pad=tree.n_pad,
                       n_levels=tree.n_levels, eta=eta,
                       dense_range=near_field_ranges(
                           bb_min[leaf], bb_max[leaf], dense_blocks))
