"""Async panel-serving runtime: queue -> scheduler -> double buffer -> fetch.

The paper's lesson (§5.4) is that H-matrix throughput on many-core hardware
comes from keeping the device saturated with batched work; Boukaram et al.
(arXiv:1902.01829) get their matvec rates by overlapping marshaling with
execution.  The synchronous panel loop (``serve.step._serve_in_panels``)
defeats both: each panel is packed, launched, and fetched to completion
before the next panel is even packed, so the device idles during host
pack/unpack and the host idles during compute.

:class:`PanelRuntime` is the asynchronous replacement shared by
``HMatrixServer`` and ``HMatrixSolveServer``:

* **Request queue.**  :meth:`submit` accepts one ``(N,)`` vector and
  returns a :class:`PanelFuture` immediately.  An optional ``max_queue``
  bounds the number of not-yet-launched requests — ``submit`` blocks until
  the scheduler drains below the cap (backpressure, so producers cannot
  outrun the device unboundedly).
* **Panel scheduler.**  A daemon thread packs pending requests into
  fixed-width panels and launches each one as soon as it is full.  JAX
  async dispatch returns device arrays without blocking, so panel k+1 is
  being packed on host while panel k still computes on device.
* **Double-buffered staging + launches.**  At most ``max_inflight``
  (default 2) panels are outstanding on device; the scheduler blocks on
  the oldest before taking new work.  One panel computes while the next
  packs — and under overload the block lets the queue coalesce into WIDER
  panels (width adapts to load) instead of flooding the device with
  narrow fixed-cost launches.  Packing cycles through one host staging
  array PER in-flight slot (the pinned-memory pattern): the pacing block
  guarantees the launch that read a buffer has completed before that
  buffer is repacked, which is what makes the zero-copy ``jnp.asarray``
  upload safe (on CPU it can alias host memory).
* **Deadline flush.**  With ``deadline_s`` set, a partial panel is flushed
  once its OLDEST request has waited that long — bounding latency under
  trickle traffic instead of waiting forever for a full panel.
* **Bucketed panel widths.**  Partial panels are padded to the smallest
  width in :func:`panel_width_buckets` (~``{R/4, R/2, R}``, each rounded
  up to the mesh device count via ``hshard.pad_panel_width`` so sharded
  meshes still get full shards) instead of always paying full-width
  padding; :meth:`precompile` warms every bucket so no real request pays
  the compile.
* **Lazy fetch.**  The launch result stays a device array inside a shared
  per-panel record; the blocking ``np.asarray`` fetch happens at most once
  per panel, deferred until the first ``PanelFuture.result()`` for that
  panel is awaited.

The pacing + staging machinery is factored into two reusable pieces so the
multi-tenant front-end (``repro.serve.tenancy.MultiTenantRuntime``) can
host MANY launch targets behind ONE scheduler with ONE global in-flight
budget:

* :class:`LaunchPacer` — the bounded in-flight FIFO (one per runtime,
  shared across every tenant of a multi-tenant runtime);
* :class:`PanelLane` — everything per launch target: width buckets, the
  staging-buffer pool (one buffer per pacer slot), zero-copy pack/pad,
  the launch call, and resolving the chunk's futures.

The scheduler and the fetch open profiler spans (``jax.profiler.
TraceAnnotation``): ``hmatrix.serve.pace_wait`` while the pacer blocks,
``hmatrix.serve.pack`` while a panel is packed, ``hmatrix.serve.launch``
around the upload and dispatch (arguments ``tenant``, ``requests``,
``width``, ``queued_ms_sum``, ``queued_ms_max``: the panel's requests'
waits from enqueue to launch), and ``hmatrix.serve.fetch`` around the
one blocking fetch of a panel.  They cost next to nothing while no
profiler runs.

Futures resolve in submission order (panels launch FIFO; columns within a
panel preserve arrival order) and — because the sync path packs identical
panels via the same width buckets — results are bit-identical to
``serve.step``'s synchronous loop (pinned by ``tests/test_serve_async.py``).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.faults import (CircuitOpenError, FaultInjector, LaneResilience,
                                NaNGuard, OverloadedError, ResiliencePolicy,
                                resolve_chaos)


def _strict_transfer_guard():
    """Disallow implicit host transfers when ``REPRO_STRICT_TRANSFERS=1``.

    The runtime twin of the hlint host-sync rule (docs/DEVICE_DISCIPLINE.md):
    wrapped around the scheduler's launch hot path so any IMPLICIT
    host<->device transfer a launch closure sneaks in (a Python scalar
    mixed into an eager op, an accidental device indexing, an eager result
    fetch) raises instead of silently serializing the pipeline.  Guards
    both host directions but NOT device-to-device: mesh resharding of the
    panel across devices is legitimate device-side work, and the invariant
    being enforced is "zero host syncs between submit and fetch".  The
    panel upload itself stays legal — ``jnp.asarray``/``jax.device_put``
    are explicit transfers, which the guard permits.  Read per call so
    tests can flip the env var at runtime.
    """
    if os.environ.get("REPRO_STRICT_TRANSFERS") == "1":
        stack = contextlib.ExitStack()
        stack.enter_context(jax.transfer_guard_host_to_device("disallow"))
        stack.enter_context(jax.transfer_guard_device_to_host("disallow"))
        return stack
    return contextlib.nullcontext()

# prefix of the runtime's profiler spans (docs/ARCHITECTURE.md, Tracing)
SPAN = "hmatrix.serve"

# width fractions of the full panel pre-compiled for partial flushes
_BUCKET_FRACTIONS = (4, 2, 1)


def panel_width_buckets(max_batch: int, n_dev: int = 1) -> tuple:
    """Increasing panel widths {~R/4, ~R/2, R}, each a multiple of ``n_dev``.

    Partial panels pad to the smallest sufficient bucket instead of the
    full width, so a deadline flush of 3 requests on a 64-wide server runs
    a 16-wide program, not a 64-wide one.  With a device mesh every bucket
    is rounded UP via ``repro.parallel.hshard.pad_panel_width`` so shards
    stay full.  Duplicates collapse (e.g. ``max_batch=4, n_dev=4`` -> one
    bucket), and the largest bucket is always exactly ``max_batch``.
    """
    if max_batch < 1:
        raise ValueError(f"panel width must be >= 1, got {max_batch}")
    if max_batch % n_dev != 0:
        raise ValueError(f"panel width {max_batch} not a multiple of the "
                         f"device count {n_dev}")
    from repro.parallel.hshard import pad_panel_width
    widths = {pad_panel_width(-(-max_batch // frac), n_dev)
              for frac in _BUCKET_FRACTIONS}
    widths.add(max_batch)
    return tuple(sorted(w for w in widths if w <= max_batch))


def width_for(count: int, widths: Sequence[int]) -> int:
    """Smallest bucket width >= ``count`` (``count`` <= the largest bucket)."""
    for w in widths:
        if w >= count:
            return w
    raise ValueError(f"{count} requests exceed the panel width {widths[-1]}")


def validate_request(vec, n: int, who: str = "request") -> np.ndarray:
    """Host-side payload validation at ``submit()`` time.

    Invalid payloads (wrong shape/dtype, non-finite values) are rejected
    HERE, on the submitting thread, with a clear error — not at launch,
    where they would fail the whole packed panel and poison every
    co-batched neighbor's future (the blast-radius bug).
    """
    if np.iscomplexobj(vec):
        raise ValueError(f"{who}: complex payload rejected — the serving "
                         f"panels are float32")
    try:
        # hlint: disable=host-sync -- client-side input normalization of host data on the submit thread; the h2d upload happens once per panel at launch
        q = np.asarray(vec, dtype=np.float32)
    except (TypeError, ValueError) as exc:
        raise ValueError(f"{who}: payload not convertible to a float32 "
                         f"vector ({exc})") from None
    if q.shape != (n,):
        raise ValueError(f"{who} shape {q.shape} != ({n},)")
    if not np.isfinite(q).all():
        raise ValueError(f"{who}: non-finite payload (NaN/Inf) rejected at "
                         f"submit — it would poison every co-batched "
                         f"request in its panel")
    return q


def _snapshot(value):
    """Deep-ish copy of a stats tree: dicts copied, deques become lists."""
    if isinstance(value, dict):
        return {k: _snapshot(v) for k, v in value.items()}
    if isinstance(value, (deque, list, tuple)):
        return [_snapshot(v) for v in value]
    return value


class _Stats(dict):
    """Stats counters: a dict for legacy attribute reads, CALLABLE for a
    consistent snapshot.

    ``runtime.stats["panels_launched"]`` keeps working (the runtime mutates
    the dict in place, under its condition lock), and ``runtime.stats()``
    returns a deep copy taken UNDER that lock — deques become plain lists —
    so a reader never observes a half-updated panel launch or iterates a
    deque another thread is appending to.
    """

    def __init__(self, lock, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._lock = lock

    def __call__(self) -> dict:
        with self._lock:
            return _snapshot(self)


class _PanelRecord:
    """One launched panel, shared by the futures of its columns.

    Holds the device result of the launch; the first ``host()`` call does
    the single blocking ``np.asarray`` fetch and caches it for every other
    column of the panel.  With a :class:`~repro.serve.faults.NaNGuard`
    attached, the fetched panel is validated (and on NaN/Inf relaunched
    once through the reference fallback) before caching; a guard failure
    is cached too, so every column future re-raises the same error without
    re-running the fallback.
    """

    __slots__ = ("_dev", "_host", "_lock", "_guard", "_exc")

    def __init__(self, dev, guard=None):
        self._dev = dev
        self._host = None
        self._lock = threading.Lock()
        self._guard = guard
        self._exc = None

    def host(self) -> np.ndarray:
        with self._lock:
            if self._exc is not None:
                raise self._exc
            if self._host is None:
                with jax.profiler.TraceAnnotation(f"{SPAN}.fetch"):
                    # hlint: disable=host-sync -- THE documented lazy fetch: one blocking transfer per panel, cached for every column future
                    out = np.asarray(self._dev)
                if self._guard is not None:
                    try:
                        out = self._guard.check(out)
                    except Exception as exc:
                        self._exc = exc
                        raise
                self._host = out
                self._dev = None
                self._guard = None
            return self._host


class PanelFuture:
    """Result handle for one submitted request.

    ``done()`` turns True when the request's panel has been LAUNCHED (the
    device result exists; it may still be computing).  ``result()`` blocks
    until then, fetches the panel to host (once, shared across the panel's
    futures), and returns this request's ``(N,)`` column.
    """

    __slots__ = ("_event", "_record", "_col", "_exc")

    def __init__(self):
        self._event = threading.Event()
        self._record = None
        self._col = 0
        self._exc = None

    def _resolve(self, record: _PanelRecord, col: int):
        self._record, self._col = record, col
        self._event.set()

    def _fail(self, exc: BaseException):
        self._exc = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError("panel not launched within timeout")
        if self._exc is not None:
            raise self._exc
        return self._record.host()[:, self._col]


class LaunchPacer:
    """Bounded in-flight launch FIFO: the pacing half of the runtime.

    At most ``max_inflight`` launches are outstanding; before taking new
    work the scheduler calls :meth:`wait_for_slot`, which retires (blocks
    on) the OLDEST outstanding launch until a slot frees.  Strictly
    single-consumer: only the owning scheduler thread may call into it, so
    it needs no lock.

    The pacer is also the STAGING-BUFFER ALIASING GUARANTEE.  ``jnp.asarray``
    on CPU can zero-copy alias host memory, so repacking a staging buffer
    races any still-computing launch that read it.  Retirement here is
    strict global FIFO, so the outstanding set is always the most recent
    ``<= max_inflight - 1`` launches (after a :meth:`wait_for_slot`).  A
    :class:`PanelLane` with ``max_inflight`` staging slots rotates back to
    a buffer only after ``max_inflight - 1`` NEWER launches of that same
    lane; if the buffer's old launch were still outstanding, those newer
    ones would be too — ``>= max_inflight`` outstanding, contradiction.
    This holds even when MANY lanes (tenants) share one pacer, which is
    what lets ``MultiTenantRuntime`` enforce one global in-flight budget
    without per-tenant pacing.
    """

    def __init__(self, max_inflight: int = 2):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        self.max_inflight = int(max_inflight)
        self._inflight: list = []   # (dev, t_commit, on_retire), FIFO order

    def __len__(self) -> int:
        return len(self._inflight)

    def wait_for_slot(self):
        """Block on the oldest outstanding launch until a slot is free.

        While blocked, arrivals keep queueing, so the next panel packs
        wider under load (width adapts to overload instead of flooding the
        device with narrow fixed-cost launches).  Retirement invokes the
        launch's ``on_retire(elapsed_s, ok)`` callback (straggler
        accounting) — exceptions from it are contained, like device ones.
        """
        if len(self._inflight) < self.max_inflight:
            return
        with jax.profiler.TraceAnnotation(f"{SPAN}.pace_wait"):
            while len(self._inflight) >= self.max_inflight:
                dev, t_commit, on_retire = self._inflight.pop(0)
                ok = True
                try:
                    # hlint: disable=host-sync -- pacing backpressure by design: block on the OLDEST launch only when the inflight window is full
                    jax.block_until_ready(dev)
                except Exception:
                    # async dispatch defers device failures to the first
                    # block: the panel's awaiters hit the same error at
                    # their np.asarray fetch — do not let it kill the
                    # scheduler thread (pending requests would strand and
                    # close() would deadlock)
                    ok = False
                if on_retire is not None:
                    try:
                        on_retire(time.monotonic() - t_commit, ok)
                    except Exception:
                        pass                # accounting must not kill the scheduler

    def commit(self, dev, on_retire=None):
        """Record one freshly dispatched launch (scheduler thread only)."""
        self._inflight.append((dev, time.monotonic(), on_retire))


class PanelLane:
    """Packing lane for ONE launch target: staging pool + width buckets.

    Owns everything per-target about getting a request chunk onto the
    device: the pre-compilable width buckets, a pool of host staging
    buffers (one per pacer slot — see :class:`LaunchPacer` for why that
    size is the aliasing guarantee), zero-copy pack/pad, the launch call,
    and resolving the chunk's futures.  ``PanelRuntime`` owns one lane;
    ``MultiTenantRuntime`` owns one lane per tenant, all paced by one
    shared :class:`LaunchPacer`.

    Resilience hooks (all optional): ``injector`` wraps the launch with a
    chaos :class:`~repro.serve.faults.FaultInjector` (scheduler-thread
    state, like the staging pool); ``fallback`` is the reference launch the
    NaN/Inf guard relaunches a poisoned panel through; ``guard_outputs``
    attaches that guard to every launched panel (costs one host copy of
    the packed panel per launch, so it is opt-in); ``on_fallback`` is the
    owning runtime's locked stats callback.

    ``store`` is the :class:`~repro.core.factor_store.FactorStore` the
    launch callable reads its factors from, when it has one (P-mode
    tenants).  The lane itself never touches the arrays — it holds the
    store so the owning runtime can do byte accounting (``nbytes()``)
    and drive the memory tier (spill cold tenants, reload before
    launch; see ``MultiTenantRuntime``).

    ``name`` (the tenant's) labels the lane's ``hmatrix.serve.launch``
    spans.
    """

    def __init__(self, n: int, max_batch: int, launch: Callable,
                 n_dev: int = 1, slots: int = 2, injector=None,
                 fallback: Callable | None = None,
                 guard_outputs: bool = False,
                 on_fallback: Callable | None = None,
                 store=None, name: str = ""):
        self.name = name
        self.n = int(n)
        self.max_batch = int(max_batch)
        self.widths = panel_width_buckets(self.max_batch, n_dev)
        self.injector = injector
        self.store = store
        self._inner = launch            # un-instrumented: warmup/compile path
        self._launch = injector.wrap(launch) if injector is not None else launch
        self._fallback = fallback
        self._guard_outputs = bool(guard_outputs)
        self._on_fallback = on_fallback
        self._staging = [np.zeros((self.n, self.max_batch), np.float32)
                         for _ in range(slots)]
        self._buf = 0

    def nbytes(self) -> int:
        """Device bytes of this lane's factor store (0 when storeless)."""
        return int(self.store.nbytes()["total"]) if self.store is not None else 0

    def launch_panel(self, chunk, pacer: LaunchPacer, on_retire=None):
        """Pack ``chunk`` into the current staging buffer, pad to its width
        bucket, launch, and resolve the chunk's futures.

        Scheduler-thread only, and only AFTER ``pacer.wait_for_slot()`` —
        that ordering is the staging-buffer reuse invariant.  Returns
        ``(w, None, dispatch_s)`` on success or ``(None, exc, dispatch_s)``
        when the launch raised.  Failure handling (fail vs retry) is the
        OWNING RUNTIME's decision, made under its lock — the lane never
        fails futures itself, so a retried chunk can simply re-enter the
        pending queue.
        """
        w = width_for(len(chunk), self.widths)
        buf = self._staging[self._buf]
        with jax.profiler.TraceAnnotation(f"{SPAN}.pack"):
            for j, (q, _, _) in enumerate(chunk):
                buf[:, j] = q
            if len(chunk) < w:
                buf[:, len(chunk):w] = 0.0          # stale pad from last reuse
        t0 = time.monotonic()
        queued_ms = [1e3 * (t0 - t) for _, _, t in chunk]
        span = jax.profiler.TraceAnnotation(
            f"{SPAN}.launch", tenant=self.name, requests=len(chunk), width=w,
            queued_ms_sum=sum(queued_ms),
            queued_ms_max=max(queued_ms, default=0.0))
        try:
            # jnp.asarray on CPU can zero-copy ALIAS the staging buffer —
            # safe ONLY because of the pacing invariant (see LaunchPacer).
            with span, _strict_transfer_guard():
                dev = self._launch(jnp.asarray(buf[:, :w]))
        except Exception as exc:
            # _buf deliberately NOT advanced: nothing holds this buffer (a
            # failing launch must raise before dispatching work that reads
            # the panel), and advancing without a pacer entry would
            # desynchronize the buffer rotation from the pacing FIFO —
            # the next rotation could then repack a buffer whose launch is
            # still computing.
            return None, exc, time.monotonic() - t0
        dispatch_s = time.monotonic() - t0
        guard = None
        if self._guard_outputs:
            # the guard must NOT retain the staging buffer (it is repacked
            # after the pacer retires this launch) nor the device result
            # (zero-copy aliasing): it keeps its own host copy
            guard = NaNGuard(buf[:, :w].copy(), len(chunk), self._fallback,
                             self._on_fallback)
        record = _PanelRecord(dev, guard)
        pacer.commit(dev, on_retire)
        self._buf = (self._buf + 1) % len(self._staging)
        for j, (_, fut, _) in enumerate(chunk):
            fut._resolve(record, j)
        return w, None, dispatch_s

    def precompile_width(self, w: int):
        """Warm the launch callable on a zero ``(n, w)`` panel (blocking).

        Uses the UN-instrumented launch: warmup must not draw from the
        chaos schedule (it would skew the injection sequence and could
        fail compiles), and the jit cache is keyed on the inner callable
        either way.
        """
        z = jnp.asarray(np.zeros((self.n, w), np.float32))
        # hlint: disable=host-sync -- blocking warmup/compile path, documented as such; never runs between submit and fetch
        jax.block_until_ready(self._inner(z))


class PanelRuntime:
    """Asynchronous micro-batching runtime over one panel launch callable.

    Parameters
    ----------
    n : int
        Request vector length (the H-matrix size).
    max_batch : int
        Full panel width.  Must already be a multiple of ``n_dev``.
    launch : Callable
        ``launch(panel)`` taking a ``(n, w)`` ``jnp`` panel (``w`` one of
        ``self.widths``) and returning the ``(n, w)`` DEVICE result without
        blocking on it (any host sync inside ``launch`` serializes the
        pipeline — see ``repro.solve.SolveInfo`` for how the solver's
        metadata stays lazy).  A failing ``launch`` must raise BEFORE
        dispatching device work that reads the panel (the staging-buffer
        reuse invariant assumes a raised launch holds no reference).
    n_dev : int, optional
        Mesh device count; every width bucket is a multiple of it.
    deadline_s : float, optional
        Flush a partial panel once its oldest request has waited this
        long.  ``None`` (default) means partial panels launch only on
        :meth:`flush` / :meth:`drain` / :meth:`close`.
    max_queue : int, optional
        Backpressure cap on not-yet-launched requests; ``submit`` blocks
        while the queue is at the cap.  ``None`` (default) = unbounded.
    max_inflight : int, optional
        Double-buffered launch depth: at most this many panels outstanding
        on device (see :class:`LaunchPacer`).
    chaos : None | str | ChaosSpec, optional
        Fault-injection schedule (``serve.faults``).  ``None`` (default)
        defers to the ``REPRO_CHAOS`` env twin; a spec string or parsed
        :class:`~repro.serve.faults.ChaosSpec` injects explicitly; an
        empty string disables injection even when the env var is set.
    resilience : ResiliencePolicy, optional
        Failure containment (retry/backoff, circuit breaker, launch
        deadline, NaN/Inf output validation).  ``None`` means no
        containment — UNLESS chaos injection is active, in which case the
        default :class:`~repro.serve.faults.ResiliencePolicy` is installed
        (an injected fault without a containment story would just be an
        outage).
    shed_above : int, optional
        Load-shedding admission budget: ``submit`` raises
        :class:`~repro.serve.faults.OverloadedError` while the queue holds
        this many requests, instead of blocking (``max_queue``) or growing
        unboundedly.  Must be >= ``max_batch``.
    fallback : Callable, optional
        Reference launch (``(n, w) -> (n, w)``, e.g. the server's
        ``use_pallas=False`` path) used for the one-shot degraded relaunch
        of a panel whose output failed NaN/Inf validation.
    store : FactorStore, optional
        The factor store the launch callable reads (P mode).  Held on the
        lane for byte accounting (``lane.nbytes()``); the multi-tenant
        runtime's memory tier spills/reloads through it (see
        ``docs/MEMORY.md``).

    Attributes
    ----------
    widths : tuple of int
        The pre-compilable panel width buckets (see
        :func:`panel_width_buckets`).
    stats : _Stats
        Dict-style counters — ``launched_widths`` (bounded deque, most
        recent panels), ``panels_launched`` (running total),
        ``max_queue_depth``, ``backpressure_waits``, plus the resilience
        set: ``retries``, ``panel_failures``, ``faults_injected`` (per-kind
        chaos tallies), ``breaker_state``, ``fallback_launches``,
        ``shed_requests``, ``slow_launches``, and ``events`` (bounded
        failure-event trace of ``(t, kind, detail)``) — mutated under the
        runtime lock.  CALL it (``runtime.stats()``) for a consistent
        snapshot copied under that lock (deques become lists); indexing
        the attribute directly keeps working but reads live state.
    """

    def __init__(self, n: int, max_batch: int, launch: Callable,
                 n_dev: int = 1, deadline_s: float | None = None,
                 max_queue: int | None = None, max_inflight: int = 2,
                 chaos=None, resilience: ResiliencePolicy | None = None,
                 shed_above: int | None = None,
                 fallback: Callable | None = None, store=None):
        if max_queue is not None and max_queue < max_batch:
            raise ValueError(f"max_queue ({max_queue}) must be >= "
                             f"max_batch ({max_batch})")
        if shed_above is not None and shed_above < max_batch:
            raise ValueError(f"shed_above ({shed_above}) must be >= "
                             f"max_batch ({max_batch}) — a full panel "
                             f"could never be admitted")
        chaos_spec = resolve_chaos(chaos)
        if resilience is None and chaos_spec is not None:
            resilience = ResiliencePolicy()
        self._cv = threading.Condition()
        self._pacer = LaunchPacer(max_inflight)
        injector = (FaultInjector(chaos_spec, "panel")
                    if chaos_spec is not None else None)
        guard = resilience is not None and resilience.validate_outputs
        self._lane = PanelLane(n, max_batch, launch, n_dev=n_dev,
                               slots=max_inflight, injector=injector,
                               fallback=fallback, guard_outputs=guard,
                               on_fallback=self._count_fallback, store=store)
        self.n = self._lane.n
        self.max_batch = self._lane.max_batch
        self.widths = self._lane.widths
        self.deadline_s = deadline_s
        self.max_queue = max_queue
        self.max_inflight = max_inflight
        self.shed_above = shed_above
        self.resilience = resilience    # frozen policy (lock-free reads ok)
        self._res = (LaneResilience(resilience, "panel")
                     if resilience is not None else None)
        # launched_widths is bounded (always-on servers launch forever);
        # panels_launched is the running total
        self.stats = _Stats(self._cv,
                            {"launched_widths": deque(maxlen=1024),
                             "panels_launched": 0, "max_queue_depth": 0,
                             "backpressure_waits": 0,
                             "retries": 0, "panel_failures": 0,
                             "faults_injected": {}, "fallback_launches": 0,
                             "shed_requests": 0, "slow_launches": 0,
                             "breaker_state": ("disabled" if self._res is None
                                               else self._res.breaker_state()),
                             "events": deque(maxlen=256)})
        self._pending: list = []        # [(np vector, PanelFuture, t_arrival)]
        self._flush_goal = 0            # launch until this many have launched
        self._launched = 0              # requests launched so far (FIFO count)
        self._submitted = 0
        self._in_launch = False
        self._closing = False
        self._closed = False
        self._thread: threading.Thread | None = None

    # -- client side --------------------------------------------------------

    def submit(self, vec) -> PanelFuture:
        """Enqueue one request vector; returns its future immediately.

        Blocks only for backpressure (``max_queue``); never for the device.
        Raises ``RuntimeError`` once the runtime has been closed,
        ``ValueError`` on an invalid payload (validated HERE so it cannot
        poison co-batched neighbors at launch),
        ``CircuitOpenError`` while the breaker quarantines the lane, and
        ``OverloadedError`` when load shedding rejects the request.
        """
        q = validate_request(vec, self.n)
        fut = PanelFuture()
        with self._cv:
            self._check_open()
            self._check_admission()
            while (self.max_queue is not None
                   and len(self._pending) >= self.max_queue):
                self.stats["backpressure_waits"] += 1
                self._cv.wait()
                self._check_open()
                self._check_admission()
            self._pending.append((q, fut, time.monotonic()))
            self._submitted += 1
            depth = len(self._pending)
            if depth > self.stats["max_queue_depth"]:
                self.stats["max_queue_depth"] = depth
            self._ensure_thread()
            self._cv.notify_all()
        return fut

    def _check_open(self):
        if self._closing:
            raise RuntimeError(
                "PanelRuntime is closed — submit() rejected; results of "
                "already-submitted requests remain fetchable via their "
                "futures, but new work needs a new runtime")

    def _check_admission(self):
        """Breaker + load-shedding admission control (caller holds _cv)."""
        if self._res is not None:
            if not self._res.allow_submit(time.monotonic()):
                raise CircuitOpenError(
                    "circuit breaker is open after consecutive panel "
                    "failures — submits fail fast until the cooldown "
                    "elapses and a half-open probe panel succeeds")
            self._sync_breaker_stat()   # open -> half_open is observable
        if self.shed_above is not None \
                and len(self._pending) >= self.shed_above:
            self.stats["shed_requests"] += 1
            self._event("shed", f"queue depth {len(self._pending)} >= "
                                f"shed_above {self.shed_above}")
            raise OverloadedError(
                f"request shed: {len(self._pending)} queued requests "
                f">= admission budget shed_above={self.shed_above} — "
                f"retry later or raise the budget")

    def _sync_breaker_stat(self):
        """Mirror the breaker state into stats (caller holds _cv)."""
        if self._res is not None:
            self.stats["breaker_state"] = self._res.breaker_state()

    def _count_fallback(self):
        # called from the FETCHING client thread (NaNGuard), not the
        # scheduler — hence it takes the lock itself
        with self._cv:
            self.stats["fallback_launches"] += 1
            self._event("fallback", "NaN/Inf panel relaunched through the "
                                    "reference path")

    def _event(self, kind: str, detail: str):
        """Append to the bounded failure-event trace (caller holds _cv)."""
        self.stats["events"].append((time.monotonic(), kind, detail))

    def flush(self):
        """Launch everything already submitted, partial panels included."""
        with self._cv:
            self._flush_goal = max(self._flush_goal, self._submitted)
            self._cv.notify_all()

    def drain(self):
        """Flush, then block until every submitted request has LAUNCHED.

        (Launched, not fetched: results are still awaited per future.)
        """
        self.flush()
        with self._cv:
            self._cv.wait_for(
                lambda: (not self._pending and not self._in_launch)
                or self._closing)

    def precompile(self):
        """Warm the launch callable on a zero panel per width bucket, so no
        real request pays the jit compile."""
        for w in self.widths:
            self._lane.precompile_width(w)

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._pending)

    def close(self):
        """Drain pending requests, then stop the scheduler thread.

        Idempotent: a second ``close()`` (or ``with``-exit after an
        explicit close) returns immediately.
        """
        with self._cv:
            if self._closed:
                return
        self.drain()
        with self._cv:
            if self._closed:            # lost a close/close race: done
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

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._scheduler, name="panel-runtime", daemon=True)
            self._thread.start()

    def _next_deadline(self) -> float | None:
        if self.deadline_s is None or not self._pending:
            return None
        return self._pending[0][2] + self.deadline_s

    def _launchable(self, now: float) -> bool:
        """Is a panel ready to take right now?  (Caller holds _cv; the
        retry-backoff gate is checked separately by the scheduler.)"""
        if len(self._pending) >= self.max_batch:
            return True                             # full panel ready
        if self._pending and self._launched < self._flush_goal:
            return True                             # flushed partial panel
        deadline = self._next_deadline()
        return deadline is not None and deadline <= now

    def _handle_failure(self, chunk, exc, now: float):
        """One panel launch failed (caller holds _cv): retry with backoff,
        fail the panel, or fail it AND open the breaker."""
        verdict = ("fail" if self._res is None
                   else self._res.decide_failure(now))
        if verdict == "retry":
            # the panel RE-ENTERS the pending queue at the front — the
            # relaunch goes back through wait_for_slot and the staging
            # rotation like any other panel (pacing FIFO preserved)
            self._pending[:0] = chunk
            self._launched -= len(chunk)
            self.stats["retries"] += 1
            self._event("retry", f"launch attempt failed ({exc!r}); panel "
                                 f"of {len(chunk)} re-queued with backoff")
            return
        for _, fut, _ in chunk:
            fut._fail(exc)
        self.stats["panel_failures"] += 1
        self._sync_breaker_stat()
        self._event("panel_failed", f"panel of {len(chunk)} failed: {exc!r}")
        if verdict == "open":
            # quarantine: everything queued fails fast (the breaker's
            # whole point is not to hold futures hostage to a dead lane)
            dropped, self._pending[:] = list(self._pending), []
            self._launched += len(dropped)
            self._event("breaker_open",
                        f"circuit opened; {len(dropped)} queued requests "
                        f"failed fast")
            err = CircuitOpenError(
                "circuit breaker opened after consecutive panel failures "
                "— queued request failed fast; resubmit after the "
                "cooldown (half-open probe)")
            err.__cause__ = exc
            for _, fut, _ in dropped:
                fut._fail(err)

    def _scheduler(self):
        while True:
            # launch pacing: block on the oldest in-flight panel BEFORE
            # taking new work (see LaunchPacer).
            self._pacer.wait_for_slot()
            with self._cv:
                while True:
                    if self._closing:
                        return
                    now = time.monotonic()
                    gate = (self._res.gate(now)
                            if self._res is not None else None)
                    if gate is None and self._launchable(now):
                        break
                    # sleep until the earliest of: retry-backoff expiry,
                    # oldest-request deadline (None = until notified)
                    wakes = [t for t in (gate, self._next_deadline())
                             if t is not None]
                    if wakes:
                        wait = min(wakes) - time.monotonic()
                        if wait > 0:
                            self._cv.wait(wait)
                        # else: loop re-evaluates with the gate expired
                    else:
                        self._cv.wait()
                chunk = self._pending[:self.max_batch]
                del self._pending[:len(chunk)]
                self._launched += len(chunk)
                self._in_launch = True
                self._cv.notify_all()               # wake backpressured submits
            w, exc, dispatch_s = None, None, 0.0
            try:
                w, exc, dispatch_s = self._lane.launch_panel(
                    chunk, self._pacer)
            finally:
                with self._cv:
                    self._in_launch = False
                    now = time.monotonic()
                    if w is not None:               # stats mutate under _cv
                        self.stats["launched_widths"].append(w)
                        self.stats["panels_launched"] += 1
                        if self._res is not None:
                            self._res.on_success()
                            self._sync_breaker_stat()
                            dl = self.resilience.launch_deadline_s
                            if dl is not None and dispatch_s > dl:
                                self.stats["slow_launches"] += 1
                                self._event(
                                    "slow_launch",
                                    f"dispatch took {dispatch_s:.4f}s > "
                                    f"deadline {dl}s")
                    elif exc is not None:
                        self._handle_failure(chunk, exc, now)
                    if self._lane.injector is not None:
                        self.stats["faults_injected"] = dict(
                            self._lane.injector.counters)
                    self._cv.notify_all()           # wake drain()
