"""H-matrix assembly and fast application (paper §2.5, §5.4, Algorithm 3).

``build_hmatrix`` constructs the cluster tree + block cluster tree and
(optionally) precomputes the ACA factors (paper's *P* mode).  ``make_apply``
returns a jitted batched executor computing ``Z = H X`` for a single vector
``x: (N,)`` or a multi-RHS panel ``X: (N, R)`` in ONE device-wide program:

  * batched rank-k products for every admissible level-group (§5.4.1) —
    in matmat form ``U (V^T X)``: two (B, m, k) x (B, k, R) contractions;
  * batched on-the-fly dense kernel-block products for the inadmissible
    leaves (§5.4.2 — dense blocks are *never* precomputed, as in the
    paper), feeding the MXU a (C, C) @ (C, R) contraction per block.

Batching over right-hand sides amortises the per-product kernel
regeneration (NP mode) and factor streaming (P mode) over all R columns —
the multi-RHS regime of Boukaram et al. 2019 and Harbrecht & Zaspel 2018.
All batch groups have static shapes, so the whole application is a single
jitted program.  Set ``use_pallas=True`` to route the hot loops through the
Pallas TPU kernels (validated against these jnp paths in tests).
``make_matvec`` is the single-vector convenience wrapper.

Every operation of a product carries a named scope in its metadata, so a
profiler trace attributes device time to the apply's parts:
``hmatrix.apply/permute_in``, ``hmatrix.apply/lowrank.L{level}/{gather,
contract,scatter}`` (``aca`` too in NP mode), ``hmatrix.apply/dense/
{gather,kernel,contract,scatter}`` and ``hmatrix.apply/permute_out``.
The fused PCG and the sharded apply inline the same body, so they carry
the same scopes.  Scopes are metadata: they change neither the numerics
nor the compiled program.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .aca import batched_aca
from .block_tree import HMatrixPlan, build_block_tree
from .clustering import ClusterTree, build_cluster_tree, permute_from_tree, permute_to_tree
from .factor_store import FactorStore, recompress_store
from .geometry import get_kernel

APPLY_SCOPE = "hmatrix.apply"

# Most bytes of the (B, m, R) float32 operand panel that one launch gathers.
# A level group's blocks, and the near field's, go in static batches under
# it (the batching of the paper's §6.5), which bounds the gathered panel and
# the product the contraction writes.  2.5 GiB keeps every launch of the
# 2-D model problem at 2^19 points and R <= 64 whole (the largest, L8, is
# (4610, 2048, 64): 2.25 GiB) and splits the 3-D Matérn problem's near
# field, (18106, 2048, 64), in four: whole, it and its product do not fit
# beside the 6.1 GiB store on a 16 GiB v5e.
LAUNCH_BYTES = 5 << 29


def launch_batches(n_blocks: int, block_bytes: int) -> list[slice]:
    """Static slices of a list of ``n_blocks`` blocks of ``block_bytes``
    each: as few batches as keep each under :data:`LAUNCH_BYTES`, as even
    as they go (one slice of the whole list where it fits)."""
    count = max(1, -(-n_blocks * block_bytes // LAUNCH_BYTES))
    size = max(1, -(-n_blocks // count))
    return [slice(i, i + size) for i in range(0, n_blocks, size)]


@dataclass(frozen=True)
class HMatrix:
    tree: ClusterTree
    plan: HMatrixPlan
    kernel: Callable
    kernel_name: str
    k: int
    # FactorStore if precomputed (paper's P mode); legacy {level: (U, V)}
    # dicts are still accepted everywhere the factors flow
    factors: FactorStore | dict | None

    @property
    def shape(self):
        return (self.tree.n, self.tree.n)

    def memory_report(self) -> dict:
        """Bytes held by the representation (metadata vs factors)."""
        if isinstance(self.factors, FactorStore):
            factor_bytes = self.factors.nbytes()["total"]
        else:
            factor_bytes = 0
            if self.factors is not None:
                for U, V in self.factors.values():
                    factor_bytes += U.size * U.dtype.itemsize + V.size * V.dtype.itemsize
        meta = sum(v.nbytes for v in self.plan.aca_levels.values())
        meta += self.plan.dense_blocks.nbytes
        dense_equiv = self.tree.n * self.tree.n * 4
        return {"factor_bytes": int(factor_bytes), "meta_bytes": int(meta),
                "dense_equivalent_bytes": int(dense_equiv)}


def _gather_cluster_points(tree: ClusterTree, level: int, ids: np.ndarray) -> jnp.ndarray:
    """Points of clusters ``ids`` at ``level``: (B, m, d) via reshape+take."""
    m = tree.n_pad >> level
    return tree.points.reshape(1 << level, m, -1)[ids]


def compute_factors(tree: ClusterTree, plan: HMatrixPlan, kernel: Callable, k: int) -> dict:
    """Precompute ACA factors for every admissible level group (P mode)."""
    factors = {}
    for level, blocks in plan.aca_levels.items():
        rp = _gather_cluster_points(tree, level, blocks[:, 0])
        cp = _gather_cluster_points(tree, level, blocks[:, 1])
        factors[level] = batched_aca(rp, cp, kernel, k)
    return factors


def build_hmatrix(coords: jnp.ndarray, kernel: str | Callable = "gaussian",
                  k: int = 16, c_leaf: int = 256, eta: float = 1.5,
                  precompute: bool = False,
                  recompress_tol: float | None = None) -> HMatrix:
    """Full H-matrix construction (paper's "setup phase").

    With ``precompute`` the factors are returned as a
    :class:`repro.core.factor_store.FactorStore` (level-grouped packed
    arrays + per-level rank tables + exact byte accounting); passing
    ``recompress_tol`` additionally SVD-truncates every level group to
    that relative tolerance at build time (see ``recompress_store``).
    """
    kernel_name = kernel if isinstance(kernel, str) else getattr(kernel, "__name__", "custom")
    kfn = get_kernel(kernel) if isinstance(kernel, str) else kernel
    tree = build_cluster_tree(coords, c_leaf=c_leaf)
    plan = build_block_tree(tree, eta=eta)
    factors = None
    if precompute:
        factors = FactorStore.from_factors(compute_factors(tree, plan, kfn, k),
                                           plan=plan)
        if recompress_tol is not None:
            recompress_store(factors, recompress_tol)
    return HMatrix(tree=tree, plan=plan, kernel=kfn, kernel_name=kernel_name,
                   k=k, factors=factors)


def diagonal_blocks(hm: HMatrix) -> jnp.ndarray:
    """Dense diagonal leaf blocks ``A[i*c:(i+1)*c, i*c:(i+1)*c]`` in TREE order.

    Returns a ``(n_leaf, c, c)`` batch of kernel blocks — the (always
    inadmissible) diagonal of the leaf partition, gathered with the same
    reshape machinery as the dense-leaf apply.  This is the raw material of
    the block-Jacobi preconditioner in ``repro.solve`` and of the diagonal
    FACTOR tasks of the H-LU engine (``repro.harith``): add ``sigma2 * I``
    and factorize.

    Ragged last leaf: the tree pads ``n`` to ``n_pad`` by duplicating the
    last point, so blocks covering the padded tail would otherwise contain
    duplicated-point rows that COUPLE real rows with phantom ones (and are
    exactly rank-deficient).  Here the pad rows/cols are masked to zero
    and their diagonal entries set to 1 — each returned block is the true
    principal submatrix of its real rows plus decoupled unit pad rows, so
    a ``sigma2``-shifted factorization is SPD for any leaf raggedness.
    """
    plan = hm.plan
    c = plan.c_leaf
    n_leaf = plan.n_pad // c
    pts = hm.tree.points.reshape(n_leaf, c, -1)
    blocks = hm.kernel(pts, pts)
    n = hm.tree.n
    if n == plan.n_pad:
        return blocks
    valid = (jnp.arange(plan.n_pad) < n).reshape(n_leaf, c)
    mask = valid[:, :, None] & valid[:, None, :]
    blocks = jnp.where(mask, blocks, 0.0)
    eye = jnp.eye(c, dtype=blocks.dtype)[None]
    return blocks + eye * (~valid)[:, :, None].astype(blocks.dtype)


# ---------------------------------------------------------------------------
# Fast application (single jitted program for x: (N,) and X: (N, R))
# ---------------------------------------------------------------------------
#
# Internally everything is rank-generic: the padded operand is carried as a
# 2-D (n_pad, R) panel (R == 1 for the matvec case) and every block batch is
# an (B, m, R) einsum / MXU contraction.


def _aca_level_apply(tree, level, blocks, U, V, x_pad, z_pad, use_pallas):
    m = tree.n_pad >> level
    r = x_pad.shape[1]
    rows, cols = jnp.asarray(blocks[:, 0]), jnp.asarray(blocks[:, 1])
    with jax.named_scope("gather"):
        x_blk = x_pad.reshape(1 << level, m, r)[cols]          # (B, m, R)
    with jax.named_scope("contract"):
        if use_pallas:
            from repro.kernels.batched_aca.ops import batched_lowrank_matmat
            y = batched_lowrank_matmat(U, V, x_blk)            # U (V^T X)
        else:
            t = jnp.einsum("bmk,bmr->bkr", V, x_blk)           # V^T X
            y = jnp.einsum("bmk,bkr->bmr", U, t)               # U T
    with jax.named_scope("scatter"):
        zl = jnp.zeros((1 << level, m, r), x_pad.dtype).at[rows].add(y)
        return z_pad + zl.reshape(-1, r)


def _dense_apply_points(points, plan, kernel, x_pad, z_pad, use_pallas,
                        dense=None):
    c = plan.c_leaf
    r = x_pad.shape[1]
    for part in launch_batches(plan.dense_blocks.shape[0],
                               c * r * x_pad.dtype.itemsize):
        z_pad = _dense_batch_apply(points, plan, kernel, x_pad, z_pad,
                                   use_pallas, part, dense)
    return z_pad


def _dense_batch_apply(points, plan, kernel, x_pad, z_pad, use_pallas, part,
                       dense):
    """One batch (``part``, a slice of ``plan.dense_blocks``) of the near
    field: regenerate (or read) its blocks and add their products."""
    blocks = plan.dense_blocks[part]
    c = plan.c_leaf
    r = x_pad.shape[1]
    n_leaf = plan.n_pad // c
    rows, cols = jnp.asarray(blocks[:, 0]), jnp.asarray(blocks[:, 1])
    pts = points.reshape(n_leaf, c, -1)
    with jax.named_scope("gather"):
        x_blk = x_pad.reshape(n_leaf, c, r)[cols]              # (B, c, R)
        if dense is None:
            pts_r, pts_c = pts[rows], pts[cols]
    if dense is not None:
        # stored dense leaves (FactorStore.dense): a straight batched MXU
        # contraction — no kernel regeneration, so no Pallas branch needed
        with jax.named_scope("contract"):
            y = jnp.einsum("bij,bjr->bir", dense[part], x_blk)
    elif use_pallas:
        from repro.kernels.batched_dense_matvec.ops import batched_kernel_matmat
        # one kernel regenerates each block and contracts it
        with jax.named_scope("kernel"):
            y = batched_kernel_matmat(pts_r, pts_c, x_blk,
                                      tree_kernel_name(kernel))
    else:
        with jax.named_scope("kernel"):
            a = kernel(pts_r, pts_c)                           # (B, c, c)
        with jax.named_scope("contract"):
            y = jnp.einsum("bij,bjr->bir", a, x_blk)
    with jax.named_scope("scatter"):
        zl = jnp.zeros((n_leaf, c, r), x_pad.dtype).at[rows].add(y)
        return z_pad + zl.reshape(-1, r)


def tree_kernel_name(kernel: Callable) -> str:
    name = getattr(kernel, "__name__", "gaussian")
    return {"gaussian_kernel": "gaussian", "matern_kernel": "matern"}.get(name, name)


def apply_in_tree_order(tree: ClusterTree, plan: HMatrixPlan, kernel: Callable,
                        k: int, use_pallas: bool, points: jnp.ndarray,
                        factors: dict | None, x_pad: jnp.ndarray) -> jnp.ndarray:
    """Core H-matrix application on a TREE-ordered padded panel.

    No permutations, no jit: this is the traceable body shared by
    :func:`make_apply` (which wraps it with the original-order
    permutations), ``repro.solve.make_solver`` (which inlines it into the
    CG ``lax.while_loop`` so the whole Krylov solve compiles to one device
    program), and ``repro.parallel.hshard`` (which runs it per device
    inside a ``shard_map``).

    Parameters
    ----------
    tree, plan, kernel, k : ClusterTree, HMatrixPlan, Callable, int
        The H-matrix structure (static under jit).
    use_pallas : bool
        Route the hot loops through the Pallas kernels.
    points : jnp.ndarray, shape (n_pad, d)
        Tree-ordered coordinates as a runtime argument (see
        :func:`make_apply` on why this must not be a traced constant).
    factors : FactorStore | dict | None
        Stored ACA factors (P mode) — a
        :class:`repro.core.factor_store.FactorStore` or a legacy
        ``level -> (U (B, m, k), V (B, m, k))`` dict — or None (NP mode:
        regenerate per product).  A store with pre-evaluated dense
        leaves (``store.dense``) also short-circuits the on-the-fly
        dense-leaf kernel regeneration.
    x_pad : jnp.ndarray, shape (n_pad, R)
        Tree-ordered operand panel (padded tail rows zero).

    Returns
    -------
    z_pad : jnp.ndarray, shape (n_pad, R)
        ``H @ x_pad`` in tree ordering.
    """
    with jax.named_scope(APPLY_SCOPE):
        z_pad = jnp.zeros_like(x_pad)
        row_bytes = x_pad.shape[1] * x_pad.dtype.itemsize
        for level, level_blocks in plan.aca_levels.items():
            m = tree.n_pad >> level
            with jax.named_scope(f"lowrank.L{level}"):
                parts = launch_batches(level_blocks.shape[0], m * row_bytes)
                for part in parts:
                    blocks = level_blocks[part]
                    if factors is not None:
                        U, V = (f[part] for f in factors[level])
                        if len(parts) > 1:
                            # a batch's factors as arrays of their own: a
                            # slice fused into the contraction made the
                            # TPU's emitter walk the whole level (3.9 s for
                            # one batch of the 3-D Matérn problem's L7, 51
                            # ms for both batches as arrays of their own)
                            U, V = jax.lax.optimization_barrier((U, V))
                    else:
                        with jax.named_scope("aca"):
                            U, V = _regenerate_factors(tree, level, blocks,
                                                       kernel, k, use_pallas,
                                                       points)
                    z_pad = _aca_level_apply(tree, level, blocks, U, V,
                                             x_pad, z_pad, use_pallas)
        with jax.named_scope("dense"):
            return _dense_apply_points(points, plan, kernel, x_pad, z_pad,
                                       use_pallas,
                                       dense=getattr(factors, "dense", None))


def _regenerate_factors(tree, level, blocks, kernel, k, use_pallas, points):
    """NP mode: one level group's ACA factors, recomputed in the product."""
    m = tree.n_pad >> level
    rp = points.reshape(1 << level, m, -1)[jnp.asarray(blocks[:, 0])]
    cp = points.reshape(1 << level, m, -1)[jnp.asarray(blocks[:, 1])]
    if use_pallas:
        from repro.kernels.batched_aca.ops import batched_aca_pallas
        return batched_aca_pallas(rp, cp, tree_kernel_name(kernel), k)
    return batched_aca(rp, cp, kernel, k)


def make_apply(hm: HMatrix, use_pallas: bool = False, mesh=None,
               shard: str = "columns") -> Callable:
    """Build the jitted batched executor ``apply(X) -> Z = H X``.

    Parameters
    ----------
    hm : HMatrix
        Assembled H-matrix (:func:`build_hmatrix`).
    use_pallas : bool, optional
        Route the hot loops (batched low-rank and dense-leaf products)
        through the Pallas TPU kernels instead of the jnp paths.
    mesh : jax.sharding.Mesh, optional
        When given, return the MULTI-DEVICE executor instead: the work is
        distributed over the mesh via ``shard_map`` (see
        ``repro.parallel.hshard.make_sharded_apply``).
    shard : {"columns", "rows"}, optional
        Sharding strategy when ``mesh`` is given.  ``"columns"`` splits the
        RHS panel along R (throughput; zero cross-device comms);
        ``"rows"`` splits the block batches by block index with a ``psum``
        of partials (latency, R=1-friendly).  Ignored without ``mesh``.

    Returns
    -------
    apply : Callable
        ``apply(x)`` with ``x`` a single vector ``(N,)`` or a panel of R
        right-hand sides ``(N, R)``, in the ORIGINAL point order; the
        result has the same shape.  One compiled program per distinct R —
        all per-block work is batched over the R columns, so the ACA
        regeneration (NP mode) / factor streaming (P mode) cost is paid
        once for the whole panel instead of once per column.

    Notes
    -----
    NP mode (``hm.factors is None``) recomputes the ACA factors inside every
    product; P mode applies the stored factors (paper §5.4 & Fig 13).

    The point array and factors are passed as runtime ARGUMENTS (not traced
    constants): with closure capture XLA constant-folds the entire on-the-fly
    kernel evaluation at compile time, silently turning NP mode into P mode.
    """
    if mesh is not None:
        from repro.parallel.hshard import make_sharded_apply
        return make_sharded_apply(hm, mesh, shard=shard, use_pallas=use_pallas)

    tree, plan, kernel, k = hm.tree, hm.plan, hm.kernel, hm.k

    @jax.jit
    def _apply(points, factors, x):
        with jax.named_scope(f"{APPLY_SCOPE}/permute_in"):
            x_pad = permute_to_tree(tree, x)                   # (n_pad, R)
        z_pad = apply_in_tree_order(tree, plan, kernel, k, use_pallas,
                                    points, factors, x_pad)
        with jax.named_scope(f"{APPLY_SCOPE}/permute_out"):
            return permute_from_tree(tree, z_pad)

    def apply(x: jnp.ndarray) -> jnp.ndarray:
        if x.ndim not in (1, 2) or x.shape[0] != tree.n:
            # explicit check: jnp gather CLAMPS out-of-range permutation
            # indices, so a wrong-length operand would silently return
            # garbage instead of erroring
            raise ValueError(f"operand shape {x.shape} incompatible with "
                             f"H-matrix of size ({tree.n}, {tree.n})")
        if x.ndim == 1:
            return _apply(tree.points, hm.factors, x[:, None])[:, 0]
        if x.shape[1] == 0:
            return jnp.zeros_like(x)
        return _apply(tree.points, hm.factors, x)

    # the program of an (N, R) panel product, lowered as jax.jit lowers it
    apply.lower = lambda x: _apply.lower(tree.points, hm.factors, x)
    return apply


def make_matvec(hm: HMatrix, use_pallas: bool = False) -> Callable:
    """Single-vector convenience wrapper over :func:`make_apply`."""
    return make_apply(hm, use_pallas=use_pallas)


def dense_matvec_oracle(coords: jnp.ndarray, kernel: str | Callable, x: jnp.ndarray) -> jnp.ndarray:
    """O(N^2) oracle for tests/benchmarks (x may be (N,) or (N, R))."""
    kfn = get_kernel(kernel) if isinstance(kernel, str) else kernel
    return kfn(coords, coords) @ x
