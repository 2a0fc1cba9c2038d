"""Multi-tenant serving: many H-matrices behind ONE panel scheduler.

The paper's central pattern — batch many small H-matrix operations into few
wide device launches — applies across *models*, not just across requests
for one model: a service holding many kernel matrices (per-dataset,
per-length-scale, per-region) must multiplex them onto one device without
one tenant's traffic starving the rest.  The scheduling flavor follows the
task-scheduling line of Börm/Christophersen/Kriemann's semi-automatic task
graphs for H-arithmetic (PAPERS.md): the unit of scheduling is a whole
batched panel launch, and fairness is enforced where the contention is —
the device launch slots — rather than per request.

:class:`MultiTenantRuntime` hosts N tenants (mixed apply- and solve-backed,
each wrapping an ``HMatrix`` with its own ``n``, width buckets, and
optional mesh) behind one scheduler thread and one global in-flight
budget:

* **Registry + per-tenant queues.**  :meth:`add_tenant` registers a
  :class:`TenantSpec` (or anything with a ``tenant_spec()`` method — both
  ``serve.step`` servers qualify) and returns a :class:`TenantHandle`
  whose ``submit(vec)`` returns the same :class:`~repro.serve.runtime.
  PanelFuture` machinery ``PanelRuntime`` uses (lazy shared per-panel
  fetch, submission-order resolution).  Each tenant keeps its own FIFO
  queue, deadline, backpressure cap, and stats.
* **Weighted deficit-round-robin panel selection.**  Every launch slot is
  one unit of cost; each scheduling round credits every *ready* tenant
  with its ``weight`` and the scheduler serves the largest accumulated
  deficit (ties to the least recently served).  A tenant with 10x the
  traffic still gets only its weighted share of launch slots while others
  are ready — and idle tenants bank no credit (their deficit resets), so
  a burst after silence cannot monopolize the device either.
* **One shared pacing FIFO.**  A single :class:`~repro.serve.runtime.
  LaunchPacer` bounds TOTAL in-flight panels across all tenants
  (``max_inflight``); each tenant's :class:`~repro.serve.runtime.
  PanelLane` holds ``max_inflight`` staging buffers, which preserves the
  staging-buffer aliasing guarantee ACROSS tenants (see ``LaunchPacer`` —
  the proof only needs strict-FIFO retirement plus per-lane pools sized
  to the budget).
* **Shared compile cache.**  Warmed panel widths are tracked per
  ``(tenant, width_bucket)``; :meth:`precompile` warms every registered
  tenant's buckets and is incremental — adding a tenant later and calling
  it again compiles only the new tenant's programs.
* **Hot add/remove.**  :meth:`add_tenant` and :meth:`remove_tenant` work
  mid-traffic; removal drains the tenant's queue (its futures all resolve)
  without stalling the other tenants, then rejects further submits.

Single-tenant behavior is unchanged: ``PanelRuntime`` shares the same
lane/pacer core, and a tenant fed the same requests as a dedicated
``PanelRuntime`` packs bit-identical panels (pinned by
``tests/test_tenancy.py``).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, replace
from typing import Callable

from repro.serve.faults import (CircuitOpenError, FaultInjector, LaneResilience,
                                OverloadedError, ResiliencePolicy,
                                StragglerMonitor, resolve_chaos)
from repro.serve.runtime import (LaunchPacer, PanelFuture, PanelLane, _Stats,
                                 validate_request)

import numpy as np


@dataclass(frozen=True)
class TenantSpec:
    """Everything the runtime needs to host one launch target.

    Parameters
    ----------
    n : int
        Request vector length (the tenant's H-matrix size).
    max_batch : int
        Full panel width for this tenant.  With ``n_dev > 1`` it must be
        a multiple of ``n_dev`` (use :func:`apply_tenant` /
        :func:`solve_tenant` or ``server.tenant_spec()`` to get the
        rounding for free).
    launch : Callable
        ``launch(panel)``: ``(n, w) -> (n, w)`` device result, non-blocking
        (same contract as :class:`repro.serve.runtime.PanelRuntime`).
    n_dev : int, optional
        Mesh device count; every width bucket is a multiple of it.
    weight : float, optional
        Fair-share weight (launch slots per scheduling round relative to
        the other tenants).  Must be > 0.
    deadline_s : float, optional
        Flush this tenant's partial panel once its oldest request has
        waited this long.
    max_queue : int, optional
        Per-tenant backpressure cap on queued-but-unlaunched requests.
    fallback : Callable, optional
        Reference launch for the NaN/Inf degraded path (``apply_tenant`` /
        ``solve_tenant`` and the servers wire their ``use_pallas=False``
        executor automatically).
    resilience : ResiliencePolicy, optional
        Per-tenant containment override; ``None`` inherits the runtime's
        policy (which defaults on when chaos injection is active).
    shed_above : int, optional
        Per-tenant load-shedding admission budget: ``submit`` raises
        ``OverloadedError`` at this queue depth instead of blocking.
    build_s : float, optional
        Construction wall time when this tenant was onboarded from raw
        coordinates (``apply_tenant(coords)`` / ``solve_tenant(coords)``
        record the on-device build here); surfaced as ``onboard_s`` in
        the per-tenant and runtime ``stats()``.
    store : FactorStore, optional
        The :class:`~repro.core.factor_store.FactorStore` the launch
        callable reads its precomputed factors from (``apply_tenant`` /
        ``solve_tenant`` wire ``hm.factors`` automatically for P-mode
        tenants).  Enables the memory tier: per-tenant ``nbytes`` in
        ``stats()``, and LRU spill/reload under the runtime's
        ``device_bytes_budget`` (see ``docs/MEMORY.md``).  NP-mode
        tenants (no precomputed factors) have nothing to spill and
        leave this None.
    precond_nbytes : int, optional
        Device bytes pinned by a solver preconditioner baked into the
        launch closures (``solve_tenant(..., precond="hlu")`` records
        the H-LU factor footprint here).  Counted against the runtime's
        ``device_bytes_budget`` for the tenant's whole lifetime: unlike
        the ``store``, the preconditioner is inlined in the compiled
        solve and can never be spilled.
    """

    n: int
    max_batch: int
    launch: Callable
    n_dev: int = 1
    weight: float = 1.0
    deadline_s: float | None = None
    max_queue: int | None = None
    fallback: Callable | None = None
    resilience: ResiliencePolicy | None = None
    shed_above: int | None = None
    build_s: float | None = None
    store: object | None = None
    precond_nbytes: int = 0

    def __post_init__(self):
        if self.weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {self.weight}")
        if self.max_queue is not None and self.max_queue < self.max_batch:
            raise ValueError(f"max_queue ({self.max_queue}) must be >= "
                             f"max_batch ({self.max_batch})")
        if self.shed_above is not None and self.shed_above < self.max_batch:
            raise ValueError(f"shed_above ({self.shed_above}) must be >= "
                             f"max_batch ({self.max_batch}) — a full panel "
                             f"could never be admitted")


def _onboard(hm, build: dict | None, spec_kw: dict):
    """Accept an assembled H-matrix OR raw coordinates.

    Raw coordinates (anything without a ``.plan`` — an ``(n, d)`` array)
    are built ON DEVICE via ``core.build_device.build_hmatrix_device``
    with the keyword options in ``build`` (kernel, k, c_leaf, eta,
    precompute, chaos, ...), and the construction wall time is recorded
    into ``spec_kw["build_s"]`` so the runtime can surface onboarding
    latency in ``stats()``.  This is the millisecond-onboarding path: a
    tenant goes from coordinates to serving without a host-side build.
    """
    if hasattr(hm, "plan"):
        return hm
    from repro.core.build_device import build_hmatrix_device_report
    hm, report = build_hmatrix_device_report(hm, **(build or {}))
    spec_kw.setdefault("build_s", report.total_s)
    return hm


def apply_tenant(hm, max_batch: int = 64, use_pallas: bool = False,
                 mesh=None, build: dict | None = None,
                 **spec_kw) -> TenantSpec:
    """Spec for an apply-backed tenant (``Z = H @ X`` query traffic).

    ``hm`` is an assembled H-matrix, or raw ``(n, d)`` coordinates to
    onboard via the on-device build (options in ``build``; construction
    time lands in ``TenantSpec.build_s``).  Builds the batched executor
    via ``core.hmatrix.make_apply`` (sharded over ``mesh`` when given)
    and rounds ``max_batch`` up to the mesh device count via
    ``hshard.pad_panel_width``.
    """
    from repro.core.hmatrix import make_apply
    from repro.parallel.hshard import mesh_device_count, pad_panel_width
    hm = _onboard(hm, build, spec_kw)
    n_dev = mesh_device_count(mesh)
    # the reference (non-Pallas) executor doubles as the NaN/Inf fallback;
    # closures are cheap — nothing compiles until a degraded panel needs it
    spec_kw.setdefault("fallback",
                       make_apply(hm, use_pallas=False, mesh=mesh))
    _wire_store(spec_kw, hm, mesh)
    return TenantSpec(n=hm.shape[0],
                      max_batch=pad_panel_width(max_batch, n_dev),
                      launch=make_apply(hm, use_pallas=use_pallas, mesh=mesh),
                      n_dev=n_dev, **spec_kw)


def _wire_store(spec_kw: dict, hm, mesh):
    """Attach ``hm.factors`` as the tenant's FactorStore when eligible.

    Only P-mode single-device tenants participate in the memory tier by
    default: NP-mode tenants have no factors to spill, and the
    row-sharded mesh executors snapshot (pad) the factor arrays at make
    time, so spilling the store would free nothing while still blocking
    launches.  An explicit ``store=`` in the spec kwargs always wins.
    """
    from repro.core.factor_store import FactorStore
    factors = getattr(hm, "factors", None)
    if (mesh is None and isinstance(factors, FactorStore)
            and factors.nbytes()["total"] > 0):
        spec_kw.setdefault("store", hm.factors)


def solve_tenant(hm, sigma2: float, max_batch: int = 8, tol: float = 1e-5,
                 max_iter: int = 300, precondition: bool = True,
                 use_pallas: bool = False, mesh=None,
                 info_log: deque | None = None,
                 precond: str | object | None = None,
                 hlu_opts: dict | None = None, **spec_kw) -> TenantSpec:
    """Spec for a solve-backed tenant (regression-fit traffic).

    One fused PCG ``while_loop`` launch per panel (``solve.make_solver``).
    ``hm`` may be raw ``(n, d)`` coordinates (see :func:`apply_tenant` —
    same on-device onboarding path, options via ``build=`` in
    ``spec_kw``).  Pass ``info_log`` (a bounded ``deque``) to retain the
    per-panel LAZY ``SolveInfo`` records; by default they are dropped
    unread (costs no device sync either way).

    ``precond`` selects the preconditioner exactly as in
    ``make_solver``: ``"bj"`` / ``"none"`` / ``"hlu"`` / a prebuilt
    :class:`~repro.harith.precond.HLUPreconditioner` (``None`` defers to
    the legacy ``precondition`` flag).  For ``"hlu"`` the factorization
    runs ONCE and is shared by the main and NaN/Inf-fallback solvers;
    its setup time lands in ``build_s`` (surfaced as ``onboard_s``) and
    its always-resident device footprint in ``precond_nbytes``, which
    the runtime charges against ``device_bytes_budget`` alongside the
    spillable store bytes.
    """
    from repro.parallel.hshard import mesh_device_count, pad_panel_width
    from repro.solve import make_solver
    hm = _onboard(hm, spec_kw.pop("build", None), spec_kw)
    n_dev = mesh_device_count(mesh)
    solve = make_solver(hm, sigma2, tol=tol, max_iter=max_iter,
                        precondition=precondition, use_pallas=use_pallas,
                        mesh=mesh, precond=precond, hlu_opts=hlu_opts)
    pre = getattr(solve, "preconditioner", None)

    def launch(panel):
        c, info = solve(panel)
        if info_log is not None:
            info_log.append(info)                   # lazy: no device sync
        return c

    # fallback shares the SAME factorization (pre is an instance, so the
    # second make_solver never re-factorizes)
    ref_solve = make_solver(hm, sigma2, tol=tol, max_iter=max_iter,
                            precondition=precondition, use_pallas=False,
                            mesh=mesh, precond=pre if pre is not None
                            else precond, hlu_opts=hlu_opts)

    def fallback(panel):
        c, _ = ref_solve(panel)                     # degraded path: no info log
        return c

    spec_kw.setdefault("fallback", fallback)
    _wire_store(spec_kw, hm, mesh)
    if pre is not None:
        spec_kw.setdefault("precond_nbytes", int(pre.nbytes()))
        # factorization is onboarding work, same as an on-device build
        spec_kw["build_s"] = (spec_kw.get("build_s") or 0.0) + pre.setup_seconds
    return TenantSpec(n=hm.shape[0],
                      max_batch=pad_panel_width(max_batch, n_dev),
                      launch=launch, n_dev=n_dev, **spec_kw)


class _Tenant:
    """Scheduler-internal per-tenant state (guarded by the runtime lock)."""

    __slots__ = ("name", "spec", "lane", "pending", "submitted", "launched",
                 "flush_goal", "in_launch", "weight", "deficit",
                 "last_served", "removing", "resident", "stats", "res")

    def __init__(self, name: str, spec: TenantSpec, slots: int, lock,
                 injector=None, resilience=None, on_fallback=None):
        self.name = name
        self.spec = spec
        guard = resilience is not None and resilience.validate_outputs
        self.lane = PanelLane(spec.n, spec.max_batch, spec.launch,
                              n_dev=spec.n_dev, slots=slots,
                              injector=injector, fallback=spec.fallback,
                              guard_outputs=guard, on_fallback=on_fallback,
                              store=spec.store, name=name)
        self.res = (LaneResilience(resilience, name)
                    if resilience is not None else None)
        self.pending: list = []         # [(np vector, PanelFuture, t_arrival)]
        self.submitted = 0
        self.launched = 0
        self.flush_goal = 0
        self.in_launch = False
        self.weight = float(spec.weight)
        self.deficit = 0.0              # banked launch-slot credit (DRR)
        self.last_served = 0            # global launch seq, for tie-breaks
        self.removing = False
        # memory tier: does this tenant's store hold device arrays?
        self.resident = (spec.store is not None
                         and not spec.store.is_spilled)
        self.stats = _Stats(lock, {"launched_widths": deque(maxlen=1024),
                                   "panels_launched": 0, "submitted": 0,
                                   "max_queue_depth": 0,
                                   "backpressure_waits": 0,
                                   "deadline_flushes": 0,
                                   "retries": 0, "panel_failures": 0,
                                   "faults_injected": {},
                                   "fallback_launches": 0,
                                   "shed_requests": 0, "slow_launches": 0,
                                   "breaker_state": ("disabled"
                                                     if self.res is None
                                                     else "closed"),
                                   "onboard_s": spec.build_s,
                                   "nbytes": self.lane.nbytes(),
                                   "precond_nbytes": spec.precond_nbytes,
                                   "resident": self.resident,
                                   "spills": 0, "reloads": 0,
                                   "reload_s": None,
                                   "events": deque(maxlen=256)})

    def drained(self) -> bool:
        return not self.pending and not self.in_launch


class TenantHandle:
    """Client-side view of one registered tenant.

    Mirrors the single-tenant ``PanelRuntime`` surface — ``submit`` /
    ``flush`` / ``drain`` / ``queue_depth`` / ``widths`` / ``stats`` — but
    scoped to this tenant inside the shared runtime.  ``stats`` is the
    same callable-dict as ``PanelRuntime.stats``: index it for live
    counters, CALL it for a locked snapshot.  The handle stays readable
    after :meth:`MultiTenantRuntime.remove_tenant`; only ``submit`` is
    rejected then.
    """

    def __init__(self, runtime: "MultiTenantRuntime", tenant: _Tenant):
        self._runtime = runtime
        self._tenant = tenant

    @property
    def name(self) -> str:
        return self._tenant.name

    @property
    def widths(self) -> tuple:
        return self._tenant.lane.widths

    @property
    def weight(self) -> float:
        # set_weight mutates this under the runtime lock; read it there too
        with self._runtime._cv:
            return self._tenant.weight

    @property
    def stats(self) -> _Stats:
        return self._tenant.stats

    def submit(self, vec) -> PanelFuture:
        return self._runtime._submit(self._tenant, vec)

    def flush(self):
        # operates on the tenant object, not the registry name: after
        # remove_tenant this is a harmless no-op (the queue was drained),
        # keeping the only-submit-is-rejected contract
        rt = self._runtime
        with rt._cv:
            self._tenant.flush_goal = max(self._tenant.flush_goal,
                                          self._tenant.submitted)
            rt._cv.notify_all()

    def drain(self):
        self.flush()
        rt = self._runtime
        with rt._cv:
            rt._cv.wait_for(lambda: self._tenant.drained() or rt._closing)

    def queue_depth(self) -> int:
        with self._runtime._cv:
            return len(self._tenant.pending)

    def set_weight(self, weight: float):
        """Adjust this tenant's fair-share weight on the fly."""
        if weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {weight}")
        with self._runtime._cv:
            self._tenant.weight = float(weight)


class MultiTenantRuntime:
    """One scheduler thread + one in-flight budget hosting many tenants.

    Parameters
    ----------
    max_inflight : int, optional
        GLOBAL double-buffered launch depth: at most this many panels
        outstanding on device across ALL tenants (one shared
        :class:`~repro.serve.runtime.LaunchPacer`).  Every tenant's
        staging pool is sized to it, which is what carries the
        staging-buffer aliasing guarantee across tenants.
    chaos : None | str | ChaosSpec, optional
        Fault-injection schedule (``serve.faults``); ``None`` defers to
        the ``REPRO_CHAOS`` env twin.  Each tenant gets an INDEPENDENT
        deterministic stream derived from the seed + its name.
    resilience : ResiliencePolicy, optional
        Default containment policy for tenants that do not set their own
        ``TenantSpec.resilience``.  Defaults on when chaos is active.
    shed_above : int, optional
        GLOBAL load-shedding admission budget: ``submit`` on any tenant
        raises ``OverloadedError`` while the TOTAL queued requests across
        tenants reach this budget (per-tenant budgets live on the spec).
    device_bytes_budget : int, optional
        Memory-pressure tier: cap on the TOTAL factor-store bytes
        resident on device across tenants.  When adding or reloading a
        store would exceed it, the least-recently-served cold tenants'
        stores are spilled to host copies (explicit ``jax.device_get``)
        until the budget holds; a spilled tenant's first request
        transparently reloads its store on the scheduler thread before
        the launch (explicit ``jax.device_put``; wall time in the
        tenant's ``reload_s`` stat), under the same chaos/retry envelope
        as the launch itself.  ``None`` (default) disables the tier.
        Tenants whose stores exceed the budget single-handedly are
        served anyway (overcommit beats an outage); the accounting in
        ``stats()["device_store_bytes"]`` stays exact either way.

    Attributes
    ----------
    stats : _Stats
        Global counters — ``panels_launched``, ``launch_order`` (bounded
        deque of tenant names in launch order; the fairness trace),
        ``tenants_added`` / ``tenants_removed``, plus the resilience
        rollups ``retries`` / ``panel_failures`` / ``shed_requests`` and
        ``straggler_tenants`` (EWMA outliers per
        :class:`~repro.serve.faults.StragglerMonitor`, fed at pacer
        retirement).  Call ``stats()`` for a locked snapshot; per-tenant
        counters (incl. ``breaker_state``, ``events``) live on each
        handle.
    """

    def __init__(self, max_inflight: int = 2, chaos=None,
                 resilience: ResiliencePolicy | None = None,
                 shed_above: int | None = None,
                 device_bytes_budget: int | None = None):
        chaos_spec = resolve_chaos(chaos)
        if resilience is None and chaos_spec is not None:
            resilience = ResiliencePolicy()
        self._cv = threading.Condition()
        self._pacer = LaunchPacer(max_inflight)
        self.max_inflight = int(max_inflight)
        self.chaos_spec = chaos_spec    # frozen (lock-free reads ok)
        self.resilience = resilience    # frozen default policy
        self.shed_above = shed_above
        # frozen config (lock-free reads ok); the mutable byte counter
        # _resident_bytes is lock-guarded like the tenant registry
        self.device_bytes_budget = device_bytes_budget
        self._monitor = StragglerMonitor()
        self._tenants: dict[str, _Tenant] = {}
        self._compiled: set = set()     # warmed (tenant name, width) pairs
        self._launch_seq = 0
        self._resident_bytes = 0        # device bytes held by tenant stores
        self.stats = _Stats(self._cv,
                            {"panels_launched": 0,
                             "launch_order": deque(maxlen=2048),
                             "tenants_added": 0, "tenants_removed": 0,
                             "retries": 0, "panel_failures": 0,
                             "shed_requests": 0, "straggler_tenants": [],
                             "onboard_s": {},
                             "evictions": 0, "reloads": 0,
                             "device_store_bytes": 0,
                             "budget_bytes": device_bytes_budget})
        self._closing = False
        self._closed = False
        self._thread: threading.Thread | None = None

    # -- registry -----------------------------------------------------------

    def add_tenant(self, name: str, spec, **overrides) -> TenantHandle:
        """Register a tenant under ``name`` and return its handle.

        ``spec`` is a :class:`TenantSpec`, or any object with a
        ``tenant_spec()`` method (both ``serve.step`` servers).  Keyword
        ``overrides`` replace spec fields (e.g. ``weight=2.0,
        deadline_s=0.01``).  Hot: works while the scheduler is serving
        other tenants.
        """
        if hasattr(spec, "tenant_spec"):
            spec = spec.tenant_spec()
        if not isinstance(spec, TenantSpec):
            raise TypeError(f"spec must be a TenantSpec or have a "
                            f"tenant_spec() method, got {type(spec)!r}")
        if overrides:
            spec = replace(spec, **overrides)
        injector = (FaultInjector(self.chaos_spec, name)
                    if self.chaos_spec is not None else None)
        resilience = (spec.resilience if spec.resilience is not None
                      else self.resilience)
        with self._cv:
            self._check_open()
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already registered")
            tenant = _Tenant(name, spec, self.max_inflight, self._cv,
                             injector=injector, resilience=resilience,
                             on_fallback=None)
            tenant.lane._on_fallback = self._make_on_fallback(tenant)
            self._tenants[name] = tenant
            self.stats["tenants_added"] += 1
            if spec.build_s is not None:
                # onboarding latency rollup: tenants built from raw
                # coordinates report their construction wall time
                self.stats["onboard_s"][name] = float(spec.build_s)
            if tenant.resident or spec.precond_nbytes:
                # memory tier: account the new store plus any pinned
                # preconditioner bytes, then spill LRU cold tenants until
                # the device-bytes budget holds again (preconditioner
                # bytes are unspillable, so only stores can be victims)
                if tenant.resident:
                    self._resident_bytes += tenant.stats["nbytes"]
                self._resident_bytes += spec.precond_nbytes
                self.stats["device_store_bytes"] = self._resident_bytes
                self._enforce_budget_locked(exempt=tenant)
            self._cv.notify_all()
            return TenantHandle(self, tenant)

    def _make_on_fallback(self, tenant: _Tenant):
        """Fetch-thread callback counting a NaN/Inf degraded relaunch."""
        def on_fallback():
            with self._cv:
                tenant.stats["fallback_launches"] += 1
                tenant.stats["events"].append(
                    (time.monotonic(), "fallback",
                     "NaN/Inf panel relaunched through the reference path"))
        return on_fallback

    def remove_tenant(self, name: str):
        """Drain ``name``'s queue, then deregister it.

        Every already-submitted request still launches and its future
        resolves; OTHER tenants keep being served throughout (this call
        waits on the shared condition, not the scheduler).  Subsequent
        ``submit`` calls on the tenant's handle raise.
        """
        with self._cv:
            tenant = self._tenants.get(name)
            if tenant is None:
                raise KeyError(f"no tenant named {name!r}")
            tenant.removing = True
            tenant.flush_goal = tenant.submitted    # drain = flush everything
            self._ensure_thread_locked()
            self._cv.notify_all()
            self._cv.wait_for(lambda: tenant.drained() or self._closing)
            self._tenants.pop(name, None)
            self._compiled = {kw for kw in self._compiled if kw[0] != name}
            self._monitor.forget(name)
            self.stats["tenants_removed"] += 1
            if tenant.resident:
                # release the departing store's device-byte accounting
                tenant.resident = False
                tenant.stats["resident"] = False
                self._resident_bytes -= tenant.stats["nbytes"]
            # pinned preconditioner bytes are released with the tenant
            # (they were never spillable, so no resident flag to clear)
            self._resident_bytes -= tenant.spec.precond_nbytes
            self.stats["device_store_bytes"] = self._resident_bytes
            self._cv.notify_all()                   # wake backpressured submits

    def tenants(self) -> tuple:
        with self._cv:
            return tuple(self._tenants)

    # -- client side --------------------------------------------------------

    def _submit(self, tenant: _Tenant, vec) -> PanelFuture:
        q = validate_request(vec, tenant.lane.n,
                             who=f"request for tenant {tenant.name!r}")
        fut = PanelFuture()
        with self._cv:
            self._check_submittable(tenant)
            self._check_admission(tenant)
            cap = tenant.spec.max_queue
            while cap is not None and len(tenant.pending) >= cap:
                tenant.stats["backpressure_waits"] += 1
                self._cv.wait()
                self._check_submittable(tenant)
                self._check_admission(tenant)
            tenant.pending.append((q, fut, time.monotonic()))
            tenant.submitted += 1
            tenant.stats["submitted"] += 1
            depth = len(tenant.pending)
            if depth > tenant.stats["max_queue_depth"]:
                tenant.stats["max_queue_depth"] = depth
            self._ensure_thread_locked()
            self._cv.notify_all()
        return fut

    def _check_open(self):
        if self._closing:
            raise RuntimeError(
                "MultiTenantRuntime is closed — submit()/add_tenant() "
                "rejected; already-submitted futures remain fetchable")

    def _check_submittable(self, tenant: _Tenant):
        self._check_open()
        if tenant.removing:
            raise RuntimeError(f"tenant {tenant.name!r} has been removed "
                               f"from the runtime — submit() rejected")

    def _check_admission(self, tenant: _Tenant):
        """Breaker + load-shedding admission control (caller holds _cv)."""
        if tenant.res is not None:
            if not tenant.res.allow_submit(time.monotonic()):
                raise CircuitOpenError(
                    f"tenant {tenant.name!r} circuit breaker is open after "
                    f"consecutive panel failures — submits fail fast until "
                    f"the cooldown elapses and a half-open probe panel "
                    f"succeeds")
            tenant.stats["breaker_state"] = tenant.res.breaker_state()
        cap = tenant.spec.shed_above
        if cap is not None and len(tenant.pending) >= cap:
            tenant.stats["shed_requests"] += 1
            self._tenant_event(tenant, "shed",
                               f"tenant queue depth {len(tenant.pending)} "
                               f">= shed_above {cap}")
            raise OverloadedError(
                f"request shed: tenant {tenant.name!r} holds "
                f"{len(tenant.pending)} queued requests >= its admission "
                f"budget shed_above={cap} — retry later")
        if self.shed_above is not None:
            total = sum(len(t.pending) for t in self._tenants.values())
            if total >= self.shed_above:
                tenant.stats["shed_requests"] += 1
                self.stats["shed_requests"] += 1
                self._tenant_event(tenant, "shed",
                                   f"global queue depth {total} >= "
                                   f"shed_above {self.shed_above}")
                raise OverloadedError(
                    f"request shed: {total} queued requests across all "
                    f"tenants >= the global admission budget "
                    f"shed_above={self.shed_above} — retry later")

    def _tenant_event(self, tenant: _Tenant, kind: str, detail: str):
        """Append to a tenant's bounded event trace (caller holds _cv)."""
        tenant.stats["events"].append((time.monotonic(), kind, detail))

    def flush(self, name: str | None = None):
        """Launch everything already submitted (one tenant, or all)."""
        with self._cv:
            for tenant in self._select(name):
                tenant.flush_goal = max(tenant.flush_goal, tenant.submitted)
            self._cv.notify_all()

    def drain(self, name: str | None = None):
        """Flush, then block until every selected request has LAUNCHED."""
        self.flush(name)
        with self._cv:
            tenants = self._select(name)
            self._cv.wait_for(
                lambda: all(t.drained() for t in tenants) or self._closing)

    def _select(self, name: str | None) -> list:
        if name is None:
            return list(self._tenants.values())
        if name not in self._tenants:
            raise KeyError(f"no tenant named {name!r}")
        return [self._tenants[name]]

    def precompile(self):
        """Warm every tenant's width buckets (shared compile cache).

        Incremental: ``(tenant, width)`` pairs already warmed — by a prior
        ``precompile`` or by real launches — are skipped, so calling this
        after :meth:`add_tenant` compiles only the new tenant's programs.
        Tenants whose store is spilled under the device-bytes budget are
        skipped too: their factors cannot flow through a trace while on
        host, and the compile happens on the first post-reload launch
        (the jit cache keys on the flattened store's shapes, which a
        reload preserves, so nothing is compiled twice).
        """
        with self._cv:
            todo = [(t.name, t.lane, w) for t in self._tenants.values()
                    if not (t.spec.store is not None
                            and t.spec.store.is_spilled)
                    for w in t.lane.widths
                    if (t.name, w) not in self._compiled]
        for name, lane, w in todo:      # blocking compiles OUTSIDE the lock
            lane.precompile_width(w)
            with self._cv:
                current = self._tenants.get(name)
                if current is not None and current.lane is lane:
                    # guard against remove_tenant + re-add of the same name
                    # mid-precompile: a stale key would make the NEW
                    # tenant's buckets look warm when they are not
                    self._compiled.add((name, w))

    def tenant_stats(self) -> dict:
        """Locked snapshot of every tenant's counters, keyed by name."""
        with self._cv:
            tenants = list(self._tenants.items())
        return {name: tenant.stats() for name, tenant in tenants}

    def close(self):
        """Drain every tenant, then stop the scheduler thread (idempotent)."""
        with self._cv:
            if self._closed:
                return
        self.drain()
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._closing = True
            self._cv.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=10)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- scheduler side -----------------------------------------------------

    def _ensure_thread_locked(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._scheduler, name="tenant-runtime", daemon=True)
            self._thread.start()

    def _ready(self, tenant: _Tenant, now: float) -> bool:
        """Does this tenant have a launchable panel right now?"""
        if not tenant.pending:
            tenant.deficit = 0.0        # classic DRR: idle banks no credit
            return False
        if tenant.res is not None and tenant.res.gate(now) is not None:
            return False                # retry backoff: not launchable yet
        if len(tenant.pending) >= tenant.lane.max_batch:
            return True                 # full panel
        if tenant.launched < tenant.flush_goal:
            return True                 # flushed / draining partial panel
        dl = tenant.spec.deadline_s
        return dl is not None and tenant.pending[0][2] + dl <= now

    def _next_wake(self, now: float) -> float | None:
        """Earliest scheduler wake time across tenants: pending deadlines
        plus retry-backoff gate expiries (None if neither applies)."""
        wakes = []
        for t in self._tenants.values():
            if not t.pending:
                continue
            if t.spec.deadline_s is not None:
                wakes.append(t.pending[0][2] + t.spec.deadline_s)
            if t.res is not None:
                gate = t.res.gate(now)
                if gate is not None:
                    wakes.append(gate)
        return min(wakes) if wakes else None

    def _pick(self, ready: list) -> _Tenant:
        """Weighted deficit round robin over the ready tenants.

        Each round credits every ready tenant with its weight; the launch
        slot goes to the largest banked deficit (ties to the least
        recently served), which then pays 1 slot of cost.  Over any
        contended interval, tenant launch counts converge to the weight
        ratio no matter how skewed the per-tenant loads are.
        """
        while True:
            eligible = [t for t in ready if t.deficit >= 1.0]
            if eligible:
                tenant = max(eligible,
                             key=lambda t: (t.deficit, -t.last_served))
                tenant.deficit -= 1.0
                return tenant
            for t in ready:             # one credit round (weights > 0, so
                t.deficit += t.weight   # some tenant reaches 1.0 eventually)
        # unreachable

    def _enforce_budget_locked(self, exempt: _Tenant | None = None,
                               incoming: int = 0):
        """Spill LRU cold tenants until the device-bytes budget holds.

        Caller holds ``_cv``.  ``incoming`` reserves room for bytes about
        to land (a store reload); ``exempt`` protects the tenant being
        served.  Victims must be resident, store-backed, and not
        ``in_launch`` — the reloading tenant is ``in_launch`` for the
        whole reload+launch window, so victim selection can never race a
        reload.  The spill itself is an explicit ``jax.device_get`` of
        already-materialised arrays (fast, and legal under
        ``REPRO_STRICT_TRANSFERS=1``, which guards only the launch
        call).  If every remaining store is pinned or the incoming store
        alone exceeds the budget, we overcommit and keep serving.
        """
        budget = self.device_bytes_budget
        if budget is None:
            return
        while self._resident_bytes + incoming > budget:
            victims = [t for t in self._tenants.values()
                       if t.resident and t.spec.store is not None
                       and not t.in_launch and t is not exempt]
            if not victims:
                break                   # overcommit beats an outage
            victim = min(victims, key=lambda t: t.last_served)  # LRU
            freed = int(victim.spec.store.spill())
            victim.resident = False
            victim.stats["resident"] = False
            victim.stats["spills"] += 1
            self._resident_bytes -= freed
            self.stats["evictions"] += 1
            self.stats["device_store_bytes"] = self._resident_bytes
            self._tenant_event(victim, "spill",
                               f"store spilled to host ({freed} bytes "
                               f"freed, LRU under {budget}-byte budget)")

    def _reload_store(self, tenant: _Tenant):
        """Reload ``tenant``'s spilled store before its launch.

        Scheduler thread, OUTSIDE the lock (an h->d transfer can take
        long enough to stall submits), after the locked pick phase set
        ``in_launch`` and reserved the bytes.  When the tenant has a
        chaos injector the reload runs under it, so injected faults hit
        the reload exactly like a launch attempt and flow into the same
        ``_handle_failure`` retry/breaker path; every injected raise
        fires BEFORE the wrapped callable, so a faulted reload leaves
        the store spilled with its host copies intact for the retry.
        Returns None on success or the exception on failure (after
        rolling back the byte reservation).
        """
        store = tenant.spec.store
        t0 = time.monotonic()
        try:
            inj = tenant.lane.injector
            if inj is not None:
                def _reload(_panel):
                    store.reload()
                    # token for the injector's NaN-poison arm; the reload
                    # itself is an exact transfer, so a poisoned token is
                    # simply discarded
                    return np.zeros((1, 1), np.float32)
                inj.wrap(_reload)(None)
            else:
                store.reload()
        except Exception as exc:
            with self._cv:
                if store.is_spilled:    # reload never happened: unreserve
                    self._resident_bytes -= tenant.stats["nbytes"]
                    self.stats["device_store_bytes"] = self._resident_bytes
            return exc
        reload_s = time.monotonic() - t0
        with self._cv:
            tenant.resident = True
            tenant.stats["resident"] = True
            tenant.stats["reloads"] += 1
            tenant.stats["reload_s"] = reload_s
            self.stats["reloads"] += 1
            self._tenant_event(tenant, "reload",
                               f"store reloaded to device in {reload_s:.4f}s")
        return None

    def _scheduler(self):
        while True:
            # global pacing: block on the oldest in-flight panel across ALL
            # tenants before taking new work — while blocked, every queue
            # keeps coalescing into wider panels (see LaunchPacer).
            self._pacer.wait_for_slot()
            with self._cv:
                tenant = None
                while tenant is None:
                    if self._closing:
                        return
                    now = time.monotonic()
                    ready = [t for t in self._tenants.values()
                             if self._ready(t, now)]
                    if ready:
                        tenant = self._pick(ready)
                        break
                    wake = self._next_wake(now)
                    if wake is not None:
                        wait = wake - time.monotonic()
                        if wait > 0:
                            self._cv.wait(wait)
                    else:
                        self._cv.wait()
                is_deadline_flush = (
                    len(tenant.pending) < tenant.lane.max_batch
                    and tenant.launched >= tenant.flush_goal)
                chunk = tenant.pending[:tenant.lane.max_batch]
                del tenant.pending[:len(chunk)]
                tenant.launched += len(chunk)
                tenant.in_launch = True
                self._launch_seq += 1
                tenant.last_served = self._launch_seq
                store = tenant.spec.store
                needs_reload = store is not None and store.is_spilled
                if needs_reload:
                    # transparent reload on first request: make room and
                    # reserve the bytes BEFORE dropping the lock, so a
                    # concurrent add_tenant sees exact accounting; we are
                    # in_launch, so we cannot be picked as a spill victim
                    self._enforce_budget_locked(
                        exempt=tenant, incoming=tenant.stats["nbytes"])
                    self._resident_bytes += tenant.stats["nbytes"]
                    self.stats["device_store_bytes"] = self._resident_bytes
                self._cv.notify_all()               # wake backpressured submits
            w, exc, dispatch_s = None, None, 0.0
            try:
                if needs_reload:
                    exc = self._reload_store(tenant)
                if exc is None:
                    w, exc, dispatch_s = tenant.lane.launch_panel(
                        chunk, self._pacer, self._make_on_retire(tenant.name))
            finally:
                with self._cv:
                    tenant.in_launch = False
                    now = time.monotonic()
                    if w is not None:               # stats mutate under _cv
                        tenant.stats["launched_widths"].append(w)
                        tenant.stats["panels_launched"] += 1
                        if is_deadline_flush:
                            tenant.stats["deadline_flushes"] += 1
                        self.stats["panels_launched"] += 1
                        self.stats["launch_order"].append(tenant.name)
                        self._compiled.add((tenant.name, w))
                        if tenant.res is not None:
                            tenant.res.on_success()
                            tenant.stats["breaker_state"] = \
                                tenant.res.breaker_state()
                            dl = tenant.res.policy.launch_deadline_s
                            if dl is not None and dispatch_s > dl:
                                tenant.stats["slow_launches"] += 1
                                self._tenant_event(
                                    tenant, "slow_launch",
                                    f"dispatch took {dispatch_s:.4f}s > "
                                    f"deadline {dl}s")
                    elif exc is not None:
                        self._handle_failure(tenant, chunk, exc, now)
                    if tenant.lane.injector is not None:
                        tenant.stats["faults_injected"] = dict(
                            tenant.lane.injector.counters)
                    self._cv.notify_all()           # wake drain()/remove

    def _handle_failure(self, tenant: _Tenant, chunk, exc, now: float):
        """One tenant panel launch failed (caller holds _cv): retry with
        backoff, fail the panel, or fail it AND quarantine the tenant."""
        verdict = ("fail" if tenant.res is None
                   else tenant.res.decide_failure(now))
        if verdict == "retry":
            # front of the TENANT queue: the relaunch re-enters the shared
            # pacing FIFO through _pick like any panel (never bypasses it),
            # and neighbors keep being served during the backoff window
            tenant.pending[:0] = chunk
            tenant.launched -= len(chunk)
            tenant.stats["retries"] += 1
            self.stats["retries"] += 1
            self._tenant_event(tenant, "retry",
                               f"launch attempt failed ({exc!r}); panel of "
                               f"{len(chunk)} re-queued with backoff")
            return
        for _, fut, _ in chunk:
            fut._fail(exc)
        tenant.stats["panel_failures"] += 1
        self.stats["panel_failures"] += 1
        self._tenant_event(tenant, "panel_failed",
                           f"panel of {len(chunk)} failed: {exc!r}")
        if tenant.res is not None:
            tenant.stats["breaker_state"] = tenant.res.breaker_state()
        if verdict == "open":
            dropped, tenant.pending[:] = list(tenant.pending), []
            tenant.launched += len(dropped)
            self._tenant_event(tenant, "breaker_open",
                               f"circuit opened; {len(dropped)} queued "
                               f"requests failed fast")
            err = CircuitOpenError(
                f"tenant {tenant.name!r} circuit breaker opened after "
                f"consecutive panel failures — queued request failed "
                f"fast; resubmit after the cooldown (half-open probe)")
            err.__cause__ = exc
            for _, fut, _ in dropped:
                fut._fail(err)

    def _make_on_retire(self, name: str):
        """Pacer-retirement callback: feed the launch's full latency
        (commit -> device-done) into the per-tenant straggler EWMA."""
        def on_retire(elapsed_s: float, ok: bool):
            with self._cv:
                self._monitor.record(name, elapsed_s)
                self.stats["straggler_tenants"] = self._monitor.stragglers()
        return on_retire
