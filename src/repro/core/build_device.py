"""On-device H-matrix construction (paper Algs. 1, 4, 6 and 7 fused).

The host pipeline (``build_hmatrix``) runs construction as eager Python:
``build_cluster_tree`` dispatches the Morton encode/sort and per-level
bounding-box reductions one eager op at a time, and ``build_block_tree``
walks the block-cluster-tree frontier with a per-level NumPy loop.  That
is fine as an *oracle* but wrong as a deployment path — construction is
exactly the part of the paper that maps onto a handful of wide launches:

* **Alg. 6** (Morton codes) + the Z-order sort: one fused encode +
  ``lexsort`` over the two uint32 code halves.
* **Alg. 7** (bounding boxes): the balanced tree turns ``reduce_by_key``
  into a dense reshape-reduce per level, parents by pairwise combine.
* **Algs. 1/4** (block cluster tree): the frontier of one level lives in
  flat index arrays; admissibility is one vectorised box test, and the
  count -> exclusive-scan -> compact advancement becomes a masked
  ``nonzero(size=...)`` compaction so every level has a static shape.

:func:`build_hmatrix_device` fuses ALL of that into ONE jitted program
(:func:`_plan_program`) whose only host interaction is a single fetch of
a packed ``int32`` metadata vector (block ids + per-level counts), then
runs factor assembly as one batched fixed-rank ACA launch per admissible
level group, or per static batch of a group too large for one launch
(paper §5.4.1 and §6.5 — the ``kernels/batched_aca`` construction entry
point) — O(levels) launches instead of O(blocks) host calls.  The
result is an :class:`~repro.core.hmatrix.HMatrix` whose plan, points,
permutation and factors are BIT-IDENTICAL to the host oracle's (pinned
by ``tests/test_build_device.py``): the structural program performs the
same exact-arithmetic ops (gathers, min/max reductions, quantisation)
and the factor stage runs the ``batched_aca`` operations of
``compute_factors``.

The build names its work for the profiler: device scopes
``hmatrix.build.plan/{morton_sort,bbox,blocktree}`` and
``hmatrix.build.aca.L{level}/{gather,aca}`` in the operations' metadata,
and host spans ``hmatrix.build.plan`` (with ``.fetch`` inside) and
``hmatrix.build.factors`` (with ``.dispatch.L{level}``, ``.wait`` and
``.store`` inside) at the points the :class:`BuildReport` timers take.

Chaos containment extends to construction: every stage launch is wrapped
in the serving stack's :class:`~repro.serve.faults.FaultInjector` when a
chaos spec is active (``chaos=`` argument or the ``REPRO_CHAOS`` env
twin), with bounded retry + backoff for raised faults and a one-shot
reference relaunch for NaN-poisoned outputs — the same containment
contract ``MultiTenantRuntime`` applies to serving launches, so a tenant
onboarded from raw coordinates (``serve.tenancy.apply_tenant``) builds
through the same fault envelope it serves under.

See ``docs/CONSTRUCTION.md`` for the stage-by-stage map and the
oracle/differential testing strategy.
"""
from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .aca import batched_aca
from .admissibility import admissible
from .block_tree import HMatrixPlan, near_field_ranges
from .clustering import ClusterTree, next_pow2
from .factor_store import FactorStore, recompress_store
from .geometry import get_kernel, KERNELS
from .hmatrix import HMatrix, launch_batches, near_field_pruned_blocks
from .morton import morton_encode

# Names of the build's device scopes (op metadata) and host spans
# (profiler annotations); docs/ARCHITECTURE.md lists what each covers.
PLAN_SCOPE = "hmatrix.build.plan"
ACA_SCOPE = "hmatrix.build.aca"
SPAN = "hmatrix.build"

# A level group's ACA goes in the static batches (``launch_batches``) of a
# float32 panel of this many columns of its blocks.  Its own working set
# per block row is about 1.2 KB on a v5e (the (m, d) points padded to 128
# lanes, the (k, m) carries), 4.6 times such a panel's 256 bytes, so one
# launch holds at most about 11.7 GiB: every level of the 2-D model problem
# at 2^19 points stays one launch, and the 3-D Matérn problem's L7 and L8
# (15.7M and 27.4M block rows) go in two and three.
ACA_PANEL_COLS = 64

# ---------------------------------------------------------------------------
# The fused structural program (Algs. 6 + 7 + 1/4 in one launch)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_pad", "n_levels", "eta"))
def _plan_program(coords, *, n_pad: int, n_levels: int, eta: float):
    """Sort + boxes + block-cluster-tree traversal as ONE device program.

    Returns ``(sorted_pts, perm, iperm, bb_min, bb_max, meta)`` where
    ``iperm`` is the inverse permutation (it stays on the device) and
    ``meta`` is a packed int32 vector: ``n_levels + 2`` counts (admissible
    blocks per level, then dense leaves) followed by the capacity-padded
    (row, col) id arrays per level (valid prefixes per the counts) — ONE
    array to fetch, sliced on host by :func:`_assemble_plan`.

    Every frontier has static capacity ``4**level`` (the balanced tree's
    worst case); validity is carried as a count + mask so the whole
    traversal jits despite data-dependent block counts.  The compaction
    order (``nonzero`` ascending, children node-major/quadrant-minor)
    matches ``block_tree.build_block_tree`` exactly, which is what makes
    the emitted plan comparable array-for-array with the host oracle.
    """
    with jax.named_scope(f"{PLAN_SCOPE}/morton_sort"):
        spts, perm, iperm = _morton_sort(coords, n_pad)
    with jax.named_scope(f"{PLAN_SCOPE}/bbox"):
        mins, maxs = _boxes(spts, n_levels)
    with jax.named_scope(f"{PLAN_SCOPE}/blocktree"):
        meta = _block_tree(mins, maxs, n_levels, eta)
    return spts, perm, iperm, tuple(mins), tuple(maxs), meta


def _morton_sort(coords, n_pad: int):
    n, d = coords.shape
    # Alg. 6: quantise on the normalised unit box (same guard as
    # clustering.build_cluster_tree), encode, stable 2-key sort.
    lo, hi = coords.min(axis=0), coords.max(axis=0)
    unit = (coords - lo) / jnp.maximum(hi - lo, 1e-30)
    code_hi, code_lo = morton_encode(unit)
    # XLA CPU's variadic sort pays per operand (a 3-operand comparator
    # costs ~4x a key-only sort), so sort the hi code halves ALONE and
    # recover the permutation by rank (searchsorted + scatter) — exact
    # whenever the hi halves are all distinct, which they are for any
    # point set whose pairwise separation exceeds the top-half quantiser
    # cell.  A device-side ``cond`` falls back to the one-launch 3-key
    # sort (index as final tiebreaker: a total order, so the unstable
    # comparator has exactly one valid output) when ties exist — both
    # branches reproduce the host's stable ``lexsort((lo, hi))``
    # permutation bit-for-bit.  Each branch also returns the inverse
    # permutation: the rank IS it, and only the tie branch pays a scatter.
    idx = jax.lax.iota(jnp.int32, n)
    shi = jax.lax.sort(code_hi, is_stable=False)
    hi_ties = (shi[1:] == shi[:-1]).any()

    def _perm_by_rank(_):
        pos = jnp.searchsorted(shi, code_hi,
                               method="scan").astype(jnp.int32)
        return jnp.zeros((n,), jnp.int32).at[pos].set(idx), pos

    def _perm_full_sort(_):
        _, _, p = jax.lax.sort((code_hi, code_lo, idx),
                               num_keys=3, is_stable=False)
        return p, jnp.zeros((n,), jnp.int32).at[p].set(idx)

    perm, iperm = jax.lax.cond(hi_ties, _perm_full_sort, _perm_by_rank, None)
    spts = coords[perm]
    if n_pad > n:
        spts = jnp.concatenate(
            [spts, jnp.broadcast_to(spts[-1], (n_pad - n, d))], axis=0)
    return spts, perm, iperm


def _boxes(spts, n_levels: int):
    n_pad, d = spts.shape
    # Alg. 7: leaf boxes by reshape-reduce, parents by pairwise combine
    # (min/max reductions are order-exact, so these match the host's
    # eager _level_bounding_boxes bitwise).
    m_leaf = n_pad >> n_levels
    cur_min = spts.reshape(1 << n_levels, m_leaf, d).min(axis=1)
    cur_max = spts.reshape(1 << n_levels, m_leaf, d).max(axis=1)
    mins, maxs = [cur_min], [cur_max]
    for _ in range(n_levels):
        cur_min = cur_min.reshape(-1, 2, d).min(axis=1)
        cur_max = cur_max.reshape(-1, 2, d).max(axis=1)
        mins.append(cur_min)
        maxs.append(cur_max)
    mins.reverse()
    maxs.reverse()
    return mins, maxs


def _block_tree(mins, maxs, n_levels: int, eta: float):
    # Algs. 1/4: level-wise frontier advancement with static capacities.
    fr = jnp.zeros((1,), jnp.int32)
    fc = jnp.zeros((1,), jnp.int32)
    n_valid = jnp.int32(1)
    counts: list = []
    blocks: list = []
    for level in range(n_levels + 1):
        cap = fr.shape[0]                       # == 4**level
        bmn, bmx = mins[level], maxs[level]
        mask = jnp.arange(cap, dtype=jnp.int32) < n_valid
        # frontier ids stay in [0, 2^level) even past the valid prefix
        # (invalid slots carry children of slot-0 parents via the
        # fill_value=0 compaction below), so the box gathers need no clamp
        adm = admissible(bmn[fr], bmx[fr], bmn[fc], bmx[fc], eta)
        adm_sel = adm & mask
        counts.append(adm_sel.sum(dtype=jnp.int32))
        adm_idx = jnp.nonzero(adm_sel, size=cap, fill_value=0)[0]
        blocks.append(fr[adm_idx])
        blocks.append(fc[adm_idx])

        split_sel = (~adm) & mask
        split_idx = jnp.nonzero(split_sel, size=cap, fill_value=0)[0]
        if level == n_levels:
            counts.append(split_sel.sum(dtype=jnp.int32))
            blocks.append(fr[split_idx])
            blocks.append(fc[split_idx])
            break
        # count -> scan -> compact: each splitting node emits 4 children
        # (2r+a, 2c+b) in quadrant order; valid parents occupy the prefix
        # of split_idx, so valid children occupy the prefix 4 * n_split.
        r, c = fr[split_idx], fc[split_idx]
        quad = jnp.arange(4, dtype=jnp.int32)
        fr = (2 * r[:, None] + quad[None, :] // 2).reshape(-1)
        fc = (2 * c[:, None] + quad[None, :] % 2).reshape(-1)
        n_valid = 4 * split_sel.sum(dtype=jnp.int32)

    return jnp.concatenate(
        [jnp.stack(counts)] + [b.astype(jnp.int32) for b in blocks])


def _assemble_plan(meta: np.ndarray, c_leaf: int, n_pad: int,
                   n_levels: int, eta: float, leaf_min: np.ndarray,
                   leaf_max: np.ndarray) -> HMatrixPlan:
    """Slice the fetched metadata vector into the host-layout plan, with
    the dense blocks' distance bounds from the fetched leaf boxes."""
    counts = meta[: n_levels + 2]
    off = n_levels + 2
    aca_levels: dict[int, np.ndarray] = {}
    for level in range(n_levels + 1):
        cap = 1 << (2 * level)                  # 4**level
        r = meta[off: off + cap]
        c = meta[off + cap: off + 2 * cap]
        off += 2 * cap
        n_adm = int(counts[level])
        if n_adm > 0:
            aca_levels[level] = np.stack([r[:n_adm], c[:n_adm]],
                                         axis=1).astype(np.int32)
    cap = 1 << (2 * n_levels)
    r = meta[off: off + cap]
    c = meta[off + cap: off + 2 * cap]
    n_dense = int(counts[n_levels + 1])
    dense = np.stack([r[:n_dense], c[:n_dense]], axis=1).astype(np.int32)
    return HMatrixPlan(aca_levels=aca_levels, dense_blocks=dense,
                       c_leaf=c_leaf, n_pad=n_pad, n_levels=n_levels,
                       eta=eta, dense_range=near_field_ranges(
                           leaf_min, leaf_max, dense))


# ---------------------------------------------------------------------------
# Chaos containment for construction launches
# ---------------------------------------------------------------------------


def _contained_stage(name: str, fn: Callable, chaos_spec, retry, rng,
                     counters: dict):
    """Run ``fn`` as ONE construction launch under the chaos envelope.

    Mirrors the serving containment contract (``serve.faults``): raised
    injected faults get bounded retry with exponential backoff; a
    NaN-poisoned launch is detected on a scalar health token and answered
    with a one-shot plain relaunch (the construction twin of the serving
    NaNGuard fallback).  The real outputs travel via ``box`` because the
    injector's poison path NaN-fills whatever the launch returns — which
    must therefore be a float array, not the int-typed plan metadata.
    """
    if chaos_spec is None:
        return fn()
    from repro.serve.faults import FaultInjector, InjectedFault

    injector = FaultInjector(chaos_spec, name)
    box: dict = {}

    def launch(_panel):
        box["out"] = fn()
        return jnp.zeros((), jnp.float32)       # health token

    wrapped = injector.wrap(launch)
    attempts = 0
    try:
        while True:
            attempts += 1
            try:
                token = wrapped(None)
            except InjectedFault:
                if retry is not None and attempts < retry.max_attempts:
                    counters["retries"] += 1
                    time.sleep(retry.delay_s(attempts, rng))
                    continue
                raise
            if not np.isfinite(jax.device_get(token)).all():
                counters["fallback_launches"] += 1
                box["out"] = fn()               # one-shot degraded relaunch
            return box["out"]
    finally:
        faults = counters.setdefault("faults_injected", {})
        for kind, hits in injector.counters.items():
            if hits:
                faults[kind] = faults.get(kind, 0) + hits


# ---------------------------------------------------------------------------
# Factor assembly: one batched ACA launch per admissible level group
# ---------------------------------------------------------------------------


def compute_factors_device(tree: ClusterTree, plan: HMatrixPlan,
                           kernel: str | Callable, k: int,
                           use_pallas: bool = False, chaos=None,
                           _counters: dict | None = None) -> dict:
    """Device-side twin of ``hmatrix.compute_factors`` (paper §5.4.1).

    One construction launch per level group, or per static batch of its
    blocks where the group is too large for one (:data:`ACA_PANEL_COLS`);
    the larger groups go first, before the store has grown, and a batched
    group's factors are joined in block order.  The cluster-point gather
    happens device-side from the tree-ordered point array, so the host
    never touches coordinates.  The default (``use_pallas=False``) runs
    the gather and then the ``batched_aca`` program inlined under the
    level's scope — the same operations as ``compute_factors``, which is
    what makes the factors bit-identical to it (pinned in tests).
    With ``use_pallas`` a registered kernel goes through the
    ``kernels/batched_aca`` construction entry point.
    """
    kernel_name = kernel if isinstance(kernel, str) else None
    kfn = get_kernel(kernel) if isinstance(kernel, str) else kernel
    chaos_spec, retry, rng = _resolve_containment(chaos)
    counters = _counters if _counters is not None else _fresh_counters()
    word = tree.points.dtype.itemsize

    def rows_of(level):
        return plan.aca_levels[level].shape[0] * (tree.n_pad >> level)

    factors = {}
    for level in sorted(plan.aca_levels, key=rows_of, reverse=True):
        level_blocks = plan.aca_levels[level]
        m = tree.n_pad >> level
        parts = []
        with jax.profiler.TraceAnnotation(f"{SPAN}.dispatch.L{level}"):
            for part in launch_batches(level_blocks.shape[0],
                                       m * ACA_PANEL_COLS * word):
                rows = jnp.asarray(level_blocks[part, 0])
                cols = jnp.asarray(level_blocks[part, 1])
                if use_pallas and kernel_name in KERNELS:
                    fn = partial(_aca_level_pallas, tree.points, rows, cols,
                                 level=level, kernel_name=kernel_name, k=k)
                else:
                    def fn(level=level, rows=rows, cols=cols):
                        rp, cp = _level_points(tree.points, rows, cols,
                                               level=level)
                        return _level_aca(rp, cp, level=level, kernel=kfn,
                                          k=k)
                parts.append(_contained_stage(f"build:factors:{level}", fn,
                                              chaos_spec, retry, rng,
                                              counters))
            factors[level] = (parts[0] if len(parts) == 1 else
                              tuple(jnp.concatenate(f) for f in zip(*parts)))
        counters["launches"] = counters.get("launches", 0) + len(parts)
    return {level: factors[level] for level in plan.aca_levels}


# One level group's launch (or one batch of it).  Each is jitted with the
# level static, so its device operations carry
# ``hmatrix.build.aca.L{level}/...`` (a scope opened around an eager call
# would not reach a jitted callee).  The shapes differ per level, so this
# is still one program per level (two where a batch is shorter).


@partial(jax.jit, static_argnames=("level",))
def _level_points(points, rows, cols, *, level: int):
    """The level's row and column cluster points, (B, m, d) each."""
    with jax.named_scope(f"{ACA_SCOPE}.L{level}/gather"):
        pts = points.reshape(1 << level, points.shape[0] >> level, -1)
        return pts[rows], pts[cols]


@partial(jax.jit, static_argnames=("level", "kernel", "k"))
def _level_aca(row_pts, col_pts, *, level: int, kernel: Callable, k: int):
    """The shared ``batched_aca`` program, inlined under the level's
    scope: the same operations, so the factors are bit-identical to the
    host path's (``compute_factors``)."""
    with jax.named_scope(f"{ACA_SCOPE}.L{level}/aca"):
        return batched_aca(row_pts, col_pts, kernel, k)


@partial(jax.jit, static_argnames=("level", "kernel_name", "k"))
def _aca_level_pallas(points, rows, cols, *, level: int, kernel_name: str,
                      k: int):
    from repro.kernels.batched_aca.ops import batched_aca_level
    with jax.named_scope(f"{ACA_SCOPE}.L{level}"):
        return batched_aca_level(points, rows, cols, level, kernel_name, k)


@partial(jax.jit, static_argnames=("c_leaf", "kernel"))
def _dense_eval(points, rows, cols, *, c_leaf: int, kernel: Callable):
    n_leaf = points.shape[0] // c_leaf
    pts = points.reshape(n_leaf, c_leaf, -1)
    return kernel(pts[rows], pts[cols])


def eval_dense_leaves(hm: HMatrix) -> jnp.ndarray:
    """Materialise every inadmissible leaf block in ONE batched launch.

    Returns a ``(n_dense, c_leaf, c_leaf)`` batch of kernel blocks in
    ``plan.dense_blocks`` order.  The executor never stores these (the
    paper evaluates dense leaves on the fly, §5.4.2); this is the
    batched-evaluation launch the differential harness and the build
    benchmark use to cover the dense half of assembly.
    """
    blocks = hm.plan.dense_blocks
    if blocks.shape[0] == 0:
        return jnp.zeros((0, hm.plan.c_leaf, hm.plan.c_leaf), jnp.float32)
    return _dense_eval(hm.tree.points, jnp.asarray(blocks[:, 0]),
                       jnp.asarray(blocks[:, 1]), c_leaf=hm.plan.c_leaf,
                       kernel=hm.kernel)


# ---------------------------------------------------------------------------
# The public builder
# ---------------------------------------------------------------------------


@dataclass
class BuildReport:
    """Stage timings + containment counters for one device build."""

    n: int
    n_pad: int
    n_levels: int
    plan_s: float                   # fused structural program + fetch
    factors_s: float                # batched ACA level-group launches
    total_s: float
    launches: int                   # device launches issued (1 + batches)
    num_aca_blocks: int
    num_dense_blocks: int
    near_field_entries: int         # kernel entries each product regenerates
    near_field_pruned_blocks: int   # dense blocks regenerated without some
                                    # of the kernel's branches
    retries: int = 0
    fallback_launches: int = 0
    faults_injected: dict = field(default_factory=dict)
    recompress_s: float = 0.0       # build-time recompression pass


def _fresh_counters() -> dict:
    return {"retries": 0, "fallback_launches": 0, "faults_injected": {}}


def _resolve_containment(chaos):
    """Chaos spec + retry policy + jitter stream for build launches."""
    from repro.serve.faults import RetryPolicy, resolve_chaos
    spec = resolve_chaos(chaos)
    if spec is None:
        return None, None, None
    return spec, RetryPolicy(), random.Random(spec.seed)


def build_hmatrix_device(coords, kernel: str | Callable = "gaussian",
                         k: int = 16, c_leaf: int = 256, eta: float = 1.5,
                         precompute: bool = False, use_pallas: bool = False,
                         chaos=None, recompress_tol: float | None = None) -> HMatrix:
    """Device-side H-matrix construction (drop-in for ``build_hmatrix``).

    Same signature and result layout as the host oracle, plus ``chaos=``
    (``None`` defers to ``REPRO_CHAOS``) for fault containment on the
    construction launches.  See :func:`build_hmatrix_device_report` for
    the instrumented variant.
    """
    hm, _ = build_hmatrix_device_report(
        coords, kernel=kernel, k=k, c_leaf=c_leaf, eta=eta,
        precompute=precompute, use_pallas=use_pallas, chaos=chaos,
        recompress_tol=recompress_tol)
    return hm


def build_hmatrix_device_report(
        coords, kernel: str | Callable = "gaussian", k: int = 16,
        c_leaf: int = 256, eta: float = 1.5, precompute: bool = False,
        use_pallas: bool = False, chaos=None,
        recompress_tol: float | None = None) -> tuple[HMatrix, BuildReport]:
    """Build on device and return ``(hmatrix, report)``.

    The report carries per-stage wall times (what ``bench_build`` and
    tenant onboarding record) and the chaos-containment counters.
    ``recompress_tol`` runs the batched algebraic recompression pass
    (``kernels/batched_recompress``) on the freshly built store before
    it is handed out; its wall time lands in ``report.recompress_s``.
    """
    kernel_name = (kernel if isinstance(kernel, str)
                   else getattr(kernel, "__name__", "custom"))
    kfn = get_kernel(kernel) if isinstance(kernel, str) else kernel
    coords = jnp.asarray(coords)
    n, d = coords.shape
    if c_leaf & (c_leaf - 1):
        raise ValueError("c_leaf must be a power of two")
    n_pad = max(next_pow2(n), c_leaf)
    n_levels = int(np.log2(n_pad // c_leaf))

    chaos_spec, retry, rng = _resolve_containment(chaos)
    counters = _fresh_counters()

    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with annotate(f"{SPAN}.plan", dim=d, kernel=kernel_name) as plan_span:
        spts, perm, iperm, bb_min, bb_max, meta = _contained_stage(
            "build:plan",
            lambda: _plan_program(coords, n_pad=n_pad, n_levels=n_levels,
                                  eta=float(eta)),
            chaos_spec, retry, rng, counters)
        with annotate(f"{SPAN}.fetch"):
            # the leaf boxes come in the same fetch as the metadata
            meta_h, leaf_min, leaf_max = jax.device_get(
                (meta, bb_min[n_levels], bb_max[n_levels]))
            plan = _assemble_plan(meta_h, c_leaf, n_pad, n_levels,
                                  float(eta), leaf_min, leaf_max)
        near_field_entries = plan.num_dense_blocks * c_leaf * c_leaf
        near_field_pruned = near_field_pruned_blocks(plan, kfn)
        plan_span.set_metadata(near_field_blocks=plan.num_dense_blocks,
                               near_field_entries=near_field_entries,
                               near_field_pruned_blocks=near_field_pruned)
        tree = ClusterTree(points=spts, perm=perm, iperm=iperm, n=n,
                           n_pad=n_pad, c_leaf=c_leaf, n_levels=n_levels,
                           bb_min=bb_min, bb_max=bb_max)
    t1 = time.perf_counter()

    factors = None
    with annotate(f"{SPAN}.factors"):
        if precompute:
            raw = compute_factors_device(tree, plan, kernel, k,
                                         use_pallas=use_pallas,
                                         chaos=chaos, _counters=counters)
            with annotate(f"{SPAN}.wait"):
                jax.block_until_ready(raw)
            with annotate(f"{SPAN}.store"):
                factors = FactorStore.from_factors(raw, plan=plan)
    t2 = time.perf_counter()

    recompress_s = 0.0
    if factors is not None and recompress_tol is not None:
        recompress_store(factors, recompress_tol, use_pallas=use_pallas)
        jax.block_until_ready(jax.tree_util.tree_leaves(factors))
        recompress_s = time.perf_counter() - t2

    hm = HMatrix(tree=tree, plan=plan, kernel=kfn, kernel_name=kernel_name,
                 k=k, factors=factors)
    report = BuildReport(
        n=n, n_pad=n_pad, n_levels=n_levels,
        plan_s=t1 - t0, factors_s=t2 - t1,
        total_s=(t2 - t0) + recompress_s,
        launches=1 + counters.get("launches", 0),
        num_aca_blocks=plan.num_aca_blocks,
        num_dense_blocks=plan.num_dense_blocks,
        near_field_entries=near_field_entries,
        near_field_pruned_blocks=near_field_pruned,
        retries=counters["retries"],
        fallback_launches=counters["fallback_launches"],
        faults_injected=counters["faults_injected"],
        recompress_s=recompress_s)
    return hm, report
