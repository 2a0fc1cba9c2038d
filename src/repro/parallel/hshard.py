"""Sharded multi-device panel execution for the H-matrix apply and solve.

The paper's thesis is total reliance on many-core hardware for the H-matrix
matvec; Harbrecht & Zaspel (arXiv:1806.11558) extend the same design to
multi-GPU clusters by distributing the work over devices, and Boukaram et
al. (arXiv:1902.01829) show the batched-tree H-matvec scales across GPUs.
This module is that step for the jax_pallas stack: it wraps the batched
executors of ``repro.core.hmatrix`` and ``repro.solve`` in a ``shard_map``
over a JAX device mesh.  Two shardings, chosen by workload shape:

Column sharding (``shard="columns"``, the throughput path).  The RHS panel
``X: (N, R)`` is split along R across the mesh; every device runs the FULL
tree-ordered apply on its ``(N, R / n_dev)`` panel slice.  Embarrassingly
parallel — zero cross-device communication in the apply.  The fused PCG
solve keeps its per-column active masks local to each shard; the only
collective is a ``psum`` all-reduce of the "any column still active"
predicate inside the ``while_loop`` cond, so every device runs the same
trip count and the loop exits globally (converged shards idle under their
frozen masks, they do not race ahead).

Row sharding (``shard="rows"``, the R=1 latency path).  With one (or few)
right-hand sides there are no columns to split, so the BLOCK BATCHES are
split instead: each ACA level group and the inadmissible dense-leaf group
are partitioned by block index across devices (padded to equal static
shares, dummy shares zero-weighted), each device computes the partial
``z`` contribution of its blocks, and one ``psum`` reduces the partials.
This shards the dominant work of a single matvec — per-block kernel
regeneration (NP mode) / factor streaming (P mode) — at the cost of one
all-reduce of the ``(n_pad, R)`` result.

Both paths pad ragged panels (``R % n_dev != 0``) with zero columns to the
next multiple of the device count and slice the pad back off; for the
solver, padded columns start converged (their active mask is False at
entry) so they cost no iterations.  On CPU, run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to exercise the mesh
path (this is what ``tests/test_shard.py`` and CI do).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.clustering import permute_from_tree, permute_to_tree
from repro.core.hmatrix import HMatrix, apply_in_tree_order, tree_kernel_name
from repro.parallel.mesh_ctx import mesh_axes, mesh_axes_size
from repro.solve.cg import build_preconditioner, pcg_tree_ordered


def make_panel_mesh(n_devices: int | None = None) -> Mesh:
    """One-axis mesh ("data") over the first ``n_devices`` local devices.

    Convenience constructor for the panel-sharding entry points; pass any
    other mesh (e.g. ``launch.mesh.make_debug_mesh``) to shard over a
    subset of its axes instead.
    """
    n = jax.device_count() if n_devices is None else n_devices
    # hlint: disable=host-sync -- np.asarray over device HANDLES (mesh construction at setup), not array data
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def pad_panel_width(r: int, n_dev: int) -> int:
    """Smallest panel width >= max(r, 1) divisible by ``n_dev``."""
    r = max(int(r), 1)
    return ((r + n_dev - 1) // n_dev) * n_dev


def mesh_device_count(mesh, axis=None) -> int:
    """Devices along ``axis`` (default ALL axes) of ``mesh``; 1 for no mesh.

    The serving layer's width-rounding contract lives here: a panel front
    (``serve.step`` servers, ``serve.tenancy`` tenants) with a mesh rounds
    its panel width UP to a multiple of this count via
    :func:`pad_panel_width`, so every ``shard_map`` shard stays full.
    """
    if mesh is None:
        return 1
    return mesh_axes_size(mesh, mesh_axes(mesh, axis))


def _replicated_specs(tree_args):
    """A spec pytree matching ``tree_args`` with every leaf replicated."""
    return jax.tree_util.tree_map(lambda _: P(), tree_args)


def _pad_columns(x: jnp.ndarray, r_pad: int) -> jnp.ndarray:
    r = x.shape[1]
    if r_pad == r:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((x.shape[0], r_pad - r), x.dtype)], axis=1)


def _check_operand(x: jnp.ndarray, n: int):
    if x.ndim not in (1, 2) or x.shape[0] != n:
        # explicit check: jnp gather CLAMPS out-of-range permutation indices,
        # so a wrong-length operand would silently return garbage
        raise ValueError(f"operand shape {x.shape} incompatible with "
                         f"H-matrix of size ({n}, {n})")


# ---------------------------------------------------------------------------
# Column sharding: split the RHS panel, replicate the operator
# ---------------------------------------------------------------------------


def make_sharded_apply(hm: HMatrix, mesh: Mesh, axis=None,
                       shard: str = "columns",
                       use_pallas: bool = False) -> Callable:
    """Multi-device ``apply(X) -> Z`` over a mesh (same contract as
    :func:`repro.core.hmatrix.make_apply`).

    Parameters
    ----------
    hm : HMatrix
        Assembled H-matrix.
    mesh : jax.sharding.Mesh
        Device mesh to execute on.
    axis : str | tuple, optional
        Mesh axis (or axes) to shard over; default ALL axes of the mesh.
    shard : {"columns", "rows"}, optional
        ``"columns"``: shard the panel along R, zero cross-device comms
        (throughput; R is padded to a multiple of the device count).
        ``"rows"``: shard the block batches by block index with a ``psum``
        of partial results (latency, R=1-friendly).
    use_pallas : bool, optional
        Route the per-device hot loops through the Pallas kernels.

    Returns
    -------
    apply : Callable
        ``apply(x)`` for ``x: (N,)`` or ``(N, R)``, original point order in
        and out, numerically matching the single-device executor.
    """
    if shard == "columns":
        return _make_colsharded_apply(hm, mesh, axis, use_pallas)
    if shard == "rows":
        return _make_rowsharded_apply(hm, mesh, axis, use_pallas)
    raise ValueError(f"shard must be 'columns' or 'rows', got {shard!r}")


def _none_to_empty(factors):
    """None factors -> {} so the pytree has a stable spec structure.

    A :class:`repro.core.factor_store.FactorStore` passes through as-is:
    it is a registered pytree, so ``_replicated_specs`` and the
    ``shard_map`` in_specs treat it exactly like the legacy dict (every
    packed level group replicated).  The sharded executors capture the
    store ONCE here — recompressing or spilling it after ``make_*`` does
    not retarget an already-built sharded apply/solve (rebuild instead;
    ``serve/tenancy.py``'s eviction tier never hands a sharded executor
    a spilled store for the same reason).
    """
    return {} if factors is None else factors


def _make_colsharded_apply(hm: HMatrix, mesh: Mesh, axis, use_pallas):
    tree, plan, kernel, k = hm.tree, hm.plan, hm.kernel, hm.k
    axes = mesh_axes(mesh, axis)
    n_dev = mesh_axes_size(mesh, axes)
    factors = _none_to_empty(hm.factors)

    def _body(points, factors, x):
        # per-device: x is this shard's (n, R / n_dev) panel slice
        x_pad = permute_to_tree(tree, x)
        z_pad = apply_in_tree_order(tree, plan, kernel, k, use_pallas,
                                    points, factors or None, x_pad)
        return permute_from_tree(tree, z_pad)

    sharded = jax.shard_map(
        _body, mesh=mesh,
        in_specs=(P(), _replicated_specs(factors), P(None, axes)),
        out_specs=P(None, axes))
    _apply = jax.jit(sharded)

    def apply(x: jnp.ndarray) -> jnp.ndarray:
        _check_operand(x, tree.n)
        if x.ndim == 2 and x.shape[1] == 0:
            return jnp.zeros_like(x)
        xp = x[:, None] if x.ndim == 1 else x
        r = xp.shape[1]
        z = _apply(tree.points, factors, _pad_columns(xp, pad_panel_width(r, n_dev)))
        return z[:, 0] if x.ndim == 1 else z[:, :r]

    return apply


# ---------------------------------------------------------------------------
# Row sharding: split the block batches, replicate the panel, psum partials
# ---------------------------------------------------------------------------


def _shard_blocks(blocks: np.ndarray, n_dev: int):
    """Pad a (B, 2) block list to equal static per-device shares.

    Returns ``(blocks_pad (B_pad, 2) int32, weights (B_pad,) float32)`` with
    ``B_pad % n_dev == 0``; dummy tail blocks alias block 0 and carry weight
    0 so their contribution is multiplied away before the scatter-add.
    """
    b = blocks.shape[0]
    b_pad = max(((b + n_dev - 1) // n_dev) * n_dev, n_dev)
    out = np.zeros((b_pad, 2), np.int32)
    out[:b] = blocks
    w = np.zeros((b_pad,), np.float32)
    w[:b] = 1.0
    return jnp.asarray(out), jnp.asarray(w)


def _pad_factors(U, V, b_pad: int):
    pad = b_pad - U.shape[0]
    if pad == 0:
        return U, V
    zu = jnp.zeros((pad,) + U.shape[1:], U.dtype)
    zv = jnp.zeros((pad,) + V.shape[1:], V.dtype)
    return jnp.concatenate([U, zu]), jnp.concatenate([V, zv])


def _aca_partial(tree, level, blk, w, U, V, x_pad, z_pad, use_pallas):
    """One device's partial ACA-level contribution (weighted local blocks)."""
    m = tree.n_pad >> level
    r = x_pad.shape[1]
    rows, cols = blk[:, 0], blk[:, 1]
    x_blk = x_pad.reshape(1 << level, m, r)[cols]              # (B_loc, m, R)
    if use_pallas:
        from repro.kernels.batched_aca.ops import batched_lowrank_matmat
        y = batched_lowrank_matmat(U, V, x_blk)
    else:
        t = jnp.einsum("bmk,bmr->bkr", V, x_blk)
        y = jnp.einsum("bmk,bkr->bmr", U, t)
    y = y * w[:, None, None]
    zl = jnp.zeros((1 << level, m, r), x_pad.dtype).at[rows].add(y)
    return z_pad + zl.reshape(-1, r)


def _dense_partial(tree, plan, kernel, points, blk, w, x_pad, z_pad,
                   use_pallas):
    """One device's partial dense-leaf contribution (weighted local blocks)."""
    c = plan.c_leaf
    r = x_pad.shape[1]
    n_leaf = plan.n_pad // c
    rows, cols = blk[:, 0], blk[:, 1]
    pts = points.reshape(n_leaf, c, -1)
    x_blk = x_pad.reshape(n_leaf, c, r)[cols]                  # (B_loc, c, R)
    if use_pallas:
        from repro.kernels.batched_dense_matvec.ops import batched_kernel_matmat
        y = batched_kernel_matmat(pts[rows], pts[cols], x_blk,
                                  tree_kernel_name(kernel))
    else:
        a = kernel(pts[rows], pts[cols])                       # (B_loc, c, c)
        y = jnp.einsum("bij,bjr->bir", a, x_blk)
    y = y * w[:, None, None]
    zl = jnp.zeros((n_leaf, c, r), x_pad.dtype).at[rows].add(y)
    return z_pad + zl.reshape(-1, r)


def _make_rowsharded_apply(hm: HMatrix, mesh: Mesh, axis, use_pallas):
    tree, plan, kernel, k = hm.tree, hm.plan, hm.kernel, hm.k
    axes = mesh_axes(mesh, axis)
    n_dev = mesh_axes_size(mesh, axes)

    # Static per-level shards: padded block lists (+ padded factors in P
    # mode), all with leading dims divisible by n_dev.
    levels = sorted(plan.aca_levels.keys())
    aca_blk, aca_w, aca_uv = {}, {}, {}
    for level in levels:
        blk, w = _shard_blocks(plan.aca_levels[level], n_dev)
        aca_blk[level], aca_w[level] = blk, w
        if hm.factors is not None:
            aca_uv[level] = _pad_factors(*hm.factors[level], blk.shape[0])
    dense_blk, dense_w = _shard_blocks(plan.dense_blocks, n_dev)
    has_dense = plan.dense_blocks.shape[0] > 0

    def _body(points, aca_blk, aca_w, aca_uv, dense_blk, dense_w, x_pad):
        z = jnp.zeros_like(x_pad)
        for level in levels:
            blk, w = aca_blk[level], aca_w[level]
            if hm.factors is not None:
                U, V = aca_uv[level]
            else:
                m = tree.n_pad >> level
                rp = points.reshape(1 << level, m, -1)[blk[:, 0]]
                cp = points.reshape(1 << level, m, -1)[blk[:, 1]]
                if use_pallas:
                    from repro.kernels.batched_aca.ops import batched_aca_pallas
                    U, V = batched_aca_pallas(rp, cp, tree_kernel_name(kernel), k)
                else:
                    from repro.core.aca import batched_aca
                    U, V = batched_aca(rp, cp, kernel, k)
            z = _aca_partial(tree, level, blk, w, U, V, x_pad, z, use_pallas)
        if has_dense:
            z = _dense_partial(tree, plan, kernel, points, dense_blk, dense_w,
                               x_pad, z, use_pallas)
        return lax.psum(z, axes)

    blk_specs = {lv: P(axes) for lv in levels}
    sharded = jax.shard_map(
        _body, mesh=mesh,
        in_specs=(P(), blk_specs, blk_specs,
                  {lv: (P(axes), P(axes)) for lv in aca_uv},
                  P(axes), P(axes), P()),
        out_specs=P())
    _apply_pad = jax.jit(sharded)

    @jax.jit
    def _permute_in(x):
        return permute_to_tree(tree, x)

    @jax.jit
    def _permute_out(z_pad):
        return permute_from_tree(tree, z_pad)

    def apply(x: jnp.ndarray) -> jnp.ndarray:
        _check_operand(x, tree.n)
        if x.ndim == 2 and x.shape[1] == 0:
            return jnp.zeros_like(x)
        xp = x[:, None] if x.ndim == 1 else x
        z_pad = _apply_pad(tree.points, aca_blk, aca_w, aca_uv,
                           dense_blk, dense_w, _permute_in(xp))
        z = _permute_out(z_pad)
        return z[:, 0] if x.ndim == 1 else z

    return apply


# ---------------------------------------------------------------------------
# Column-sharded fused PCG solve
# ---------------------------------------------------------------------------


def make_sharded_solver(hm: HMatrix, sigma2: float, mesh: Mesh, axis=None,
                        tol: float = 1e-5, max_iter: int = 300,
                        precondition: bool = True,
                        use_pallas: bool = False) -> Callable:
    """Multi-device ``solve(F) -> (C, SolveInfo)`` over a mesh (same
    contract as :func:`repro.solve.make_solver`).

    The RHS panel is sharded column-wise: each device runs the fused
    active-mask PCG ``while_loop`` (:func:`repro.solve.cg.pcg_tree_ordered`)
    on its own column slice with its own per-column masks.  The single
    collective is the ``psum`` all-reduce of the "any column active"
    predicate in the loop cond — every device therefore runs the same trip
    count as the single-device solver would on the full panel, and the
    numerics per column are IDENTICAL to the unsharded path (each column's
    CG never mixes columns).

    Parameters
    ----------
    hm, sigma2, tol, max_iter, precondition, use_pallas
        As :func:`repro.solve.make_solver`.
    mesh : jax.sharding.Mesh
        Device mesh to execute on.
    axis : str | tuple, optional
        Mesh axis (or axes) to shard over; default ALL axes of the mesh.

    Returns
    -------
    solve : Callable
        ``solve(F)`` for ``F: (N,)`` or ``(N, R)``; ragged R is padded to a
        multiple of the device count with zero columns (which start
        converged and cost no iterations) and sliced back off.
    """
    from repro.solve.cg import SolveInfo

    tree, plan, kernel, k = hm.tree, hm.plan, hm.kernel, hm.k
    n = tree.n
    tol2 = float(tol) * float(tol)
    axes = mesh_axes(mesh, axis)
    n_dev = mesh_axes_size(mesh, axes)
    chol = build_preconditioner(hm, sigma2, use_pallas) if precondition else None
    factors = _none_to_empty(hm.factors)
    chol_tuple = () if chol is None else (chol,)

    def reduce_any(active):
        return lax.psum(jnp.any(active).astype(jnp.int32), axes) > 0

    def _body(points, factors, chol_arg, b):
        # per-device: b is this shard's (n, R / n_dev) column slice
        b_pad = permute_to_tree(tree, b)
        x, it, iters_col, rr = pcg_tree_ordered(
            tree, plan, kernel, k, use_pallas, sigma2, tol2, max_iter,
            points, factors or None, chol_arg[0] if chol_arg else None,
            b_pad, reduce_any)
        return permute_from_tree(tree, x), it, iters_col, jnp.sqrt(rr)

    sharded = jax.shard_map(
        _body, mesh=mesh,
        in_specs=(P(), _replicated_specs(factors),
                  _replicated_specs(chol_tuple), P(None, axes)),
        # `it` is replicated by construction: the psum'd predicate gives
        # every device the same trip count
        out_specs=(P(None, axes), P(), P(axes), P(axes)))
    _solve = jax.jit(sharded)

    def solve(f: jnp.ndarray):
        _check_operand(f, n)
        fp = f[:, None] if f.ndim == 1 else f
        r = fp.shape[1]
        x, it, iters_col, res = _solve(
            tree.points, factors, chol_tuple,
            _pad_columns(fp, pad_panel_width(r, n_dev)))
        x = x[:, :r]
        # lazy SolveInfo over the device arrays (pad columns sliced off on
        # device): no host sync in the launch path, launches can overlap
        info = SolveInfo(it, iters_col[:r], res[:r], tol)
        return (x[:, 0] if f.ndim == 1 else x), info

    return solve
