"""Fault injection + containment (`repro.serve.faults`): chaos spec grammar,
deterministic schedules, retry/backoff recovery, breaker state machine,
tenant isolation under a failing neighbor, NaN fallback, payload rejection,
load shedding, straggler accounting — plus the supervisor/straggler tests
that moved here with the code from ``runtime.fault_tolerance``.

Every runtime constructed here pins ``chaos=`` explicitly (a spec or ``""``)
so the assertions hold unchanged when CI re-runs this file under a global
``REPRO_CHAOS`` environment.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.faults import (BreakerPolicy, ChaosSpec, CircuitBreaker,
                                CircuitOpenError, FaultInjector,
                                InjectedFault, LaneResilience, NaNGuard,
                                NaNPanelError, OverloadedError, ResiliencePolicy,
                                RetryPolicy, StragglerMonitor,
                                TransientInjectedFault, chaos_from_env,
                                resolve_chaos, run_with_restarts)
from repro.serve.runtime import PanelRuntime
from repro.serve.tenancy import MultiTenantRuntime, TenantSpec

_double = jax.jit(lambda panel: panel * 2.0)
_triple = jax.jit(lambda panel: panel * 3.0)


def _fail_fast_policy(threshold=3, cooldown_s=0.05):
    """No retries: every panel failure counts against the breaker at once."""
    return ResiliencePolicy(retry=None,
                            breaker=BreakerPolicy(threshold=threshold,
                                                  cooldown_s=cooldown_s))


# ---------------------------------------------------------------------------
# chaos spec grammar + env twin
# ---------------------------------------------------------------------------


def test_chaos_spec_parse_full_grammar():
    spec = ChaosSpec.parse("error=0.1, transient=0.2:3, nan=0.05,"
                           "latency=0.1:0.02, seed=7")
    assert spec == ChaosSpec(error_rate=0.1, transient_rate=0.2,
                             transient_fails=3, nan_rate=0.05,
                             latency_rate=0.1, latency_s=0.02, seed=7)
    # any subset, including none
    assert ChaosSpec.parse("seed=3") == ChaosSpec(seed=3)
    assert ChaosSpec.parse("") == ChaosSpec()


@pytest.mark.parametrize("bad", [
    "error=1.5",                  # rate out of [0, 1]
    "error=0.6,transient=0.6",    # rates sum > 1 (they partition one draw)
    "transient=0.1:0",            # fail count < 1
    "latency=0.1:-1",             # negative latency
    "error",                      # not key=value
    "frobnicate=1",               # unknown key
    "error=abc",                  # unparsable value
])
def test_chaos_spec_rejects_bad_fields(bad):
    with pytest.raises(ValueError):
        ChaosSpec.parse(bad)


def test_chaos_env_twin_and_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    assert chaos_from_env() is None
    monkeypatch.setenv("REPRO_CHAOS", "transient=0.25,seed=9")
    assert chaos_from_env() == ChaosSpec(transient_rate=0.25, seed=9)
    # None defers to the env; "" explicitly disables; strings parse;
    # specs pass through
    assert resolve_chaos(None) == ChaosSpec(transient_rate=0.25, seed=9)
    assert resolve_chaos("") is None
    assert resolve_chaos("nan=0.5") == ChaosSpec(nan_rate=0.5)
    spec = ChaosSpec(error_rate=0.1)
    assert resolve_chaos(spec) is spec
    with pytest.raises(TypeError):
        resolve_chaos(42)


# ---------------------------------------------------------------------------
# deterministic injection schedules
# ---------------------------------------------------------------------------


def _schedule(spec, name, n=60):
    """Outcome sequence of one injector stream over n launch attempts."""
    inj = FaultInjector(spec, name)
    chaotic = inj.wrap(_double)
    panel = jnp.ones((4, 2), jnp.float32)
    out = []
    for _ in range(n):
        try:
            res = chaotic(panel)
        except TransientInjectedFault:
            out.append("T")
        except InjectedFault:
            out.append("E")
        else:
            out.append("N" if np.isnan(np.asarray(res)).any() else ".")
    return out, inj


def test_injection_schedule_is_deterministic_per_seed_and_lane():
    spec = ChaosSpec.parse("error=0.1,transient=0.15:2,nan=0.1,seed=11")
    s1, inj1 = _schedule(spec, "lane-a")
    s2, inj2 = _schedule(spec, "lane-a")
    assert s1 == s2                               # same seed+lane: same schedule
    assert inj1.counters == inj2.counters
    s3, _ = _schedule(spec, "lane-b")
    assert s3 != s1                               # independent per-lane streams
    s4, _ = _schedule(ChaosSpec.parse("error=0.1,transient=0.15:2,nan=0.1,"
                                      "seed=12"), "lane-a")
    assert s4 != s1                               # seed moves the schedule
    # every injected fault is tallied
    assert inj1.counters["error"] == s1.count("E")
    assert inj1.counters["transient"] == s1.count("T")
    assert inj1.counters["nan"] == s1.count("N")
    assert inj1.total() == len(s1) - s1.count(".")


def test_transient_fault_fails_k_consecutive_attempts_then_recovers():
    spec = ChaosSpec(transient_rate=1.0, transient_fails=3)
    inj = FaultInjector(spec, "lane")
    chaotic = inj.wrap(_double)
    panel = jnp.ones((2, 1), jnp.float32)
    for _ in range(3):                            # the hit + 2 pending fails
        with pytest.raises(TransientInjectedFault):
            chaotic(panel)
    # transient_rate=1.0 re-draws a NEW hit right after recovery, so the
    # pattern is periodic: fail, fail, fail, fail, ...; with rate < 1 the
    # pending counter is what guarantees recovery — check it directly
    assert inj._pending_fails == 0


def test_injected_latency_delays_launch():
    spec = ChaosSpec(latency_rate=1.0, latency_s=0.05)
    inj = FaultInjector(spec, "lane")
    chaotic = inj.wrap(_double)
    t0 = time.monotonic()
    out = chaotic(jnp.ones((2, 1), jnp.float32))
    assert time.monotonic() - t0 >= 0.05
    assert inj.counters["latency"] == 1
    np.testing.assert_array_equal(np.asarray(out), np.full((2, 1), 2.0))


# ---------------------------------------------------------------------------
# retry/backoff: recovery and exhaustion
# ---------------------------------------------------------------------------


def test_transient_fault_recovers_via_retry_with_correct_results():
    """A transient launch failure is retried with backoff; the SAME panel
    relaunches and its futures resolve with correct values — callers never
    see the fault."""
    # seed=0 / lane "panel" at rate 0.5 draws F F . F . — panel 1 fails
    # twice then recovers, panel 2 fails once then recovers (deterministic)
    rt = PanelRuntime(8, 2, _double, chaos="transient=0.5:1,seed=0",
                      resilience=ResiliencePolicy(
                          retry=RetryPolicy(max_attempts=3,
                                            backoff_s=0.001),
                          breaker=None))
    with rt:
        futs = [rt.submit(np.full(8, j, np.float32)) for j in range(4)]
        rt.flush()
        outs = [f.result(timeout=60) for f in futs]
    for j, out in enumerate(outs):
        np.testing.assert_array_equal(out, np.full(8, 2.0 * j, np.float32))
    assert rt.stats["retries"] >= 2               # both panels hit + retried
    assert rt.stats["panel_failures"] == 0
    assert rt.stats["faults_injected"]["transient"] >= 2
    kinds = [k for _, k, _ in rt.stats["events"]]
    assert "retry" in kinds


def test_retry_exhaustion_propagates_the_launch_error():
    """A permanently failing launch exhausts max_attempts and fails its
    futures with the original error."""
    calls = []

    def broken(panel):
        calls.append(1)
        raise RuntimeError("device on fire")

    rt = PanelRuntime(8, 2, broken, chaos="",
                      resilience=ResiliencePolicy(
                          retry=RetryPolicy(max_attempts=3,
                                            backoff_s=0.001),
                          breaker=None))
    f = rt.submit(np.zeros(8, np.float32))
    rt.flush()
    with pytest.raises(RuntimeError, match="device on fire"):
        f.result(timeout=60)
    rt.close()
    assert len(calls) == 3                        # total attempts, bounded
    assert rt.stats["retries"] == 2
    assert rt.stats["panel_failures"] == 1


def test_backoff_delay_grows_exponentially_with_jitter_bound():
    pol = RetryPolicy(max_attempts=5, backoff_s=0.01, backoff_mult=2.0,
                      jitter=0.5)
    import random
    rng = random.Random(0)
    for attempt in (1, 2, 3):
        base = 0.01 * 2.0 ** (attempt - 1)
        for _ in range(20):
            d = pol.delay_s(attempt, rng)
            assert base <= d <= base * 1.5


# ---------------------------------------------------------------------------
# circuit breaker: open / fail-fast / half-open probe / reclose
# ---------------------------------------------------------------------------


def test_circuit_breaker_state_machine():
    br = CircuitBreaker(BreakerPolicy(threshold=2, cooldown_s=0.1))
    assert br.state == "closed" and br.allow_submit(0.0)
    assert br.on_panel_failure(1.0) is False      # 1 of 2
    assert br.on_panel_failure(1.0) is True       # threshold: opens
    assert br.state == "open" and not br.allow_submit(1.05)
    assert br.allow_submit(1.2)                   # cooled down: half-open
    assert br.state == "half_open"
    assert br.on_panel_failure(1.3) is True       # probe failed: reopens
    assert br.state == "open"
    assert br.allow_submit(1.5)                   # cool down again
    br.on_panel_success()                         # probe succeeded
    assert br.state == "closed" and br.failures == 0


def test_breaker_opens_fails_fast_and_recloses_after_probe():
    """Runtime-level breaker lifecycle: consecutive panel failures open the
    breaker (queued futures fail fast, submits rejected); after the cooldown
    a half-open probe panel recloses it and serving resumes."""
    state = {"broken": True}

    def flaky(panel):
        if state["broken"]:
            raise RuntimeError("lane down")
        return _double(panel)

    rt = PanelRuntime(8, 2, flaky, chaos="",
                      resilience=_fail_fast_policy(threshold=2,
                                                   cooldown_s=0.05))
    with rt:
        f1 = rt.submit(np.zeros(8, np.float32))
        rt.flush()
        with pytest.raises(RuntimeError, match="lane down"):
            f1.result(timeout=30)                 # failure 1 of 2
        assert rt.stats["breaker_state"] == "closed"
        f2 = rt.submit(np.zeros(8, np.float32))
        f3 = rt.submit(np.zeros(8, np.float32))   # packs into f2's panel
        f4 = rt.submit(np.zeros(8, np.float32))   # still queued when it opens
        rt.flush()
        for f in (f2, f3):                        # failure 2: breaker opens
            with pytest.raises(RuntimeError, match="lane down"):
                f.result(timeout=30)
        # everything still queued failed fast with CircuitOpenError
        with pytest.raises(CircuitOpenError):
            f4.result(timeout=30)
        assert rt.stats["breaker_state"] == "open"
        with pytest.raises(CircuitOpenError):
            rt.submit(np.zeros(8, np.float32))    # fail fast at admission
        kinds = [k for _, k, _ in rt.stats["events"]]
        assert "breaker_open" in kinds
        # cooldown -> half-open probe -> success -> reclosed
        state["broken"] = False
        time.sleep(0.06)
        probe = rt.submit(np.ones(8, np.float32))
        rt.flush()
        np.testing.assert_array_equal(probe.result(timeout=30),
                                      np.full(8, 2.0, np.float32))
        assert rt.stats["breaker_state"] == "closed"


def test_half_open_probe_failure_reopens_without_retry():
    """A failing half-open probe reopens the breaker immediately — probing
    panels never burn the retry budget on a lane that is still down."""
    calls = []

    def broken(panel):
        calls.append(1)
        raise RuntimeError("still down")

    rt = PanelRuntime(8, 2, broken, chaos="",
                      resilience=ResiliencePolicy(
                          retry=RetryPolicy(max_attempts=4,
                                            backoff_s=0.001),
                          breaker=BreakerPolicy(threshold=1,
                                                cooldown_s=0.05)))
    with rt:
        f = rt.submit(np.zeros(8, np.float32))
        rt.flush()
        with pytest.raises(RuntimeError):
            f.result(timeout=30)                  # retries, then opens
        attempts_first = len(calls)
        assert attempts_first == 4                # full retry budget used
        time.sleep(0.06)
        probe = rt.submit(np.zeros(8, np.float32))
        rt.flush()
        with pytest.raises(RuntimeError):
            probe.result(timeout=30)
        assert len(calls) == attempts_first + 1   # probe: ONE attempt only
        assert rt.stats["breaker_state"] == "open"


# ---------------------------------------------------------------------------
# tenant isolation: a failing neighbor cannot degrade healthy tenants
# ---------------------------------------------------------------------------


def _p95(xs):
    return float(np.percentile(np.asarray(xs), 95))


def _healthy_latencies(mtr_kwargs, with_bad_neighbor, n_requests=40):
    """Run a healthy echo tenant (optionally next to a permanently failing
    one) and return its per-request submit->result latencies + stats."""
    with MultiTenantRuntime(chaos="", **mtr_kwargs) as mtr:
        good = mtr.add_tenant("good", TenantSpec(16, 4, _double))
        bad_futs = []
        if with_bad_neighbor:
            def broken(panel):
                raise RuntimeError("neighbor on fire")
            bad = mtr.add_tenant("bad", TenantSpec(
                8, 2, broken, resilience=_fail_fast_policy(threshold=3)))
            bad_futs = [bad.submit(np.zeros(8, np.float32))
                        for _ in range(8)]
        t_submit, futs = [], []
        for j in range(n_requests):
            t_submit.append(time.monotonic())
            futs.append(good.submit(np.full(16, j, np.float32)))
        mtr.flush()
        lat = []
        for j, f in enumerate(futs):
            out = f.result(timeout=120)
            lat.append(time.monotonic() - t_submit[j])
            np.testing.assert_array_equal(
                out, np.full(16, 2.0 * j, np.float32))
        stats = {"good": good.stats(), "global": mtr.stats(),
                 "bad": bad.stats() if with_bad_neighbor else None}
        for f in bad_futs:                        # every bad future FAILED,
            with pytest.raises(RuntimeError):     # none hangs
                f.result(timeout=30)
    return lat, stats


def test_failing_tenant_trips_breaker_healthy_neighbor_unaffected():
    """Acceptance: a permanently failing tenant trips its breaker; the
    healthy neighbor's results are exact, none of its futures fail, its
    launches are not starved, and its p95 latency stays within a generous
    bound of the fault-free baseline."""
    base_lat, _ = _healthy_latencies({}, with_bad_neighbor=False)
    lat, stats = _healthy_latencies({}, with_bad_neighbor=True)
    assert stats["bad"]["breaker_state"] == "open"
    assert stats["bad"]["panel_failures"] >= 3    # threshold reached
    # healthy tenant: full service, zero failures, zero retries burned
    assert stats["good"]["panels_launched"] == 10
    assert stats["good"]["panel_failures"] == 0
    assert stats["good"]["retries"] == 0
    # the bad tenant stopped consuming launch slots once quarantined
    order = stats["global"]["launch_order"]
    assert order.count("bad") <= 4                # <= threshold + probe
    assert order.count("good") == 10
    # p95 bound: generous (CI timing noise) but catches order-of-magnitude
    # degradation like head-of-line blocking behind the dead tenant
    assert _p95(lat) <= max(10 * _p95(base_lat), 1.0)


# ---------------------------------------------------------------------------
# acceptance: transient chaos is invisible to callers
# ---------------------------------------------------------------------------


def test_multitenant_bit_identical_under_recoverable_chaos():
    """5% transient faults, all recoverable within the retry budget: a
    MultiTenantRuntime returns BIT-identical results to a fault-free run
    and not one future fails."""
    rng = np.random.RandomState(0)
    reqs = {"a": [rng.randn(16).astype(np.float32) for _ in range(64)],
            "b": [rng.randn(8).astype(np.float32) for _ in range(64)]}

    def run(chaos):
        with MultiTenantRuntime(chaos=chaos) as mtr:
            ta = mtr.add_tenant("a", TenantSpec(16, 2, _double))
            tb = mtr.add_tenant("b", TenantSpec(8, 2, _triple))
            fa = [ta.submit(q) for q in reqs["a"]]
            fb = [tb.submit(q) for q in reqs["b"]]
            mtr.flush()
            outs = ([f.result(timeout=120) for f in fa],
                    [f.result(timeout=120) for f in fb])
            return outs, mtr.stats(), ta.stats(), tb.stats()

    clean, *_ = run(chaos="")
    chaotic, gstats, astats, bstats = run(chaos="transient=0.05:1,seed=3")
    for side in (0, 1):
        for out_clean, out_chaos in zip(clean[side], chaotic[side]):
            np.testing.assert_array_equal(out_clean, out_chaos)
    assert gstats["panel_failures"] == 0          # zero futures failed
    assert gstats["retries"] >= 1                 # chaos actually injected
    injected = (sum(astats["faults_injected"].values())
                + sum(bstats["faults_injected"].values()))
    assert injected >= 1
    assert astats["breaker_state"] == "closed"
    assert bstats["breaker_state"] == "closed"


def test_server_async_matches_sync_under_zero_rate_env_chaos(monkeypatch):
    """REPRO_CHAOS with zero rates arms the whole harness (injector wired,
    default resilience, NaN guard) without injecting — async results stay
    bit-identical to the synchronous panel loop."""
    from repro.core import build_hmatrix, halton
    from repro.serve.step import HMatrixServer
    monkeypatch.setenv("REPRO_CHAOS", "seed=7")
    rng = np.random.RandomState(1)
    pts = halton(300, 2)
    hm = build_hmatrix(pts, "gaussian", k=16, c_leaf=128, precompute=True)
    queries = [jnp.asarray(rng.randn(300).astype(np.float32))
               for _ in range(9)]
    with HMatrixServer(hm, max_batch=4) as srv:
        sync = srv.serve(queries)
        outs = [f.result(timeout=120) for f in srv.serve_async(queries)]
        stats = srv.runtime.stats()
    for a, b in zip(sync, outs):
        np.testing.assert_array_equal(a, b)
    assert stats["faults_injected"] == {"error": 0, "transient": 0,
                                        "nan": 0, "latency": 0}
    assert stats["breaker_state"] == "closed"
    assert stats["retries"] == 0 and stats["fallback_launches"] == 0


# ---------------------------------------------------------------------------
# NaN/Inf output validation + degraded fallback
# ---------------------------------------------------------------------------


def test_nan_poisoned_panel_falls_back_to_reference_result():
    """nan=1.0 chaos poisons every launch; the fetch-time guard detects it
    and relaunches the SAME panel through the reference fallback — callers
    get the reference answer, and the fallback is counted."""
    rt = PanelRuntime(8, 2, _double, chaos="nan=1.0,seed=0",
                      fallback=_double)
    with rt:
        futs = [rt.submit(np.full(8, j + 1.0, np.float32))
                for j in range(4)]
        rt.flush()
        outs = [f.result(timeout=60) for f in futs]
    for j, out in enumerate(outs):
        np.testing.assert_array_equal(
            out, np.full(8, 2.0 * (j + 1.0), np.float32))
    assert rt.stats["faults_injected"]["nan"] == 2
    assert rt.stats["fallback_launches"] == 2     # once per PANEL, not column
    assert rt.stats["panel_failures"] == 0        # contained, not failed


def test_nan_without_fallback_raises_nan_panel_error():
    rt = PanelRuntime(8, 2, _double, chaos="nan=1.0,seed=0")  # no fallback
    f = rt.submit(np.ones(8, np.float32))
    rt.flush()
    with pytest.raises(NaNPanelError, match="no reference fallback"):
        f.result(timeout=60)
    rt.close()


def test_nan_guard_failure_is_cached_across_column_futures():
    calls = []

    def counting_fallback(panel):
        calls.append(1)
        return _double(panel)

    guard = NaNGuard(np.ones((4, 2), np.float32), 2, counting_fallback, None)
    bad = np.full((4, 2), np.nan, np.float32)
    out = guard.check(bad)
    np.testing.assert_array_equal(out, np.full((4, 2), 2.0, np.float32))
    assert len(calls) == 1
    # a still-broken fallback raises instead of looping
    broken_guard = NaNGuard(np.ones((4, 2), np.float32), 2,
                            lambda p: p * jnp.nan, None)
    with pytest.raises(NaNPanelError, match="fallback still produced"):
        broken_guard.check(bad)


# ---------------------------------------------------------------------------
# payload validation at submit(): blast radius zero
# ---------------------------------------------------------------------------


def test_invalid_payloads_rejected_at_submit_neighbors_unharmed():
    """Wrong length, wrong dtype, non-convertible, and non-finite payloads
    raise AT SUBMIT with a clear error; requests co-batched around the
    rejects still resolve correctly."""
    with PanelRuntime(8, 4, _double, chaos="") as rt:
        good = [rt.submit(np.full(8, 1.0, np.float32))]
        with pytest.raises(ValueError, match=r"shape \(9,\) != \(8,\)"):
            rt.submit(np.zeros(9, np.float32))
        with pytest.raises(ValueError, match="complex"):
            rt.submit(np.zeros(8, np.complex64))
        with pytest.raises(ValueError, match="not convertible"):
            rt.submit(["not", "a", "vector", 0, 0, 0, 0, 0])
        with pytest.raises(ValueError, match="non-finite"):
            rt.submit(np.array([np.nan] + [0.0] * 7, np.float32))
        with pytest.raises(ValueError, match="non-finite"):
            rt.submit(np.array([np.inf] + [0.0] * 7, np.float32))
        good.append(rt.submit(np.full(8, 2.0, np.float32)))
        rt.flush()
        for j, f in enumerate(good):
            np.testing.assert_array_equal(
                f.result(timeout=30), np.full(8, 2.0 * (j + 1), np.float32))
        assert rt.stats["panels_launched"] == 1   # one clean co-batched panel


def test_tenant_submit_validation_names_the_tenant():
    with MultiTenantRuntime(chaos="") as mtr:
        t = mtr.add_tenant("alpha", TenantSpec(8, 2, _double))
        with pytest.raises(ValueError, match="tenant 'alpha'"):
            t.submit(np.zeros(5, np.float32))
        f = t.submit(np.ones(8, np.float32))
        mtr.flush()
        np.testing.assert_array_equal(f.result(timeout=30),
                                      np.full(8, 2.0, np.float32))


# ---------------------------------------------------------------------------
# load shedding: admission control beyond the budget
# ---------------------------------------------------------------------------


def test_runtime_load_shedding_rejects_beyond_budget():
    blocker, started = threading.Event(), threading.Event()

    def gated(panel):
        started.set()
        blocker.wait(timeout=30)
        return _double(panel)

    rt = PanelRuntime(8, 2, gated, chaos="", shed_above=4)
    try:
        futs = [rt.submit(np.full(8, j, np.float32)) for j in range(2)]
        assert started.wait(timeout=30)           # panel 1 launched + stuck
        futs += [rt.submit(np.full(8, j, np.float32))
                 for j in range(2, 6)]            # queue fills to the budget
        with pytest.raises(OverloadedError, match="shed"):
            rt.submit(np.zeros(8, np.float32))
        assert rt.stats["shed_requests"] == 1
        kinds = [k for _, k, _ in rt.stats["events"]]
        assert "shed" in kinds
    finally:
        blocker.set()
    with rt:
        rt.flush()
        for j, f in enumerate(futs):              # admitted work still served
            np.testing.assert_array_equal(
                f.result(timeout=60), np.full(8, 2.0 * j, np.float32))
    with pytest.raises(ValueError, match="shed_above"):
        PanelRuntime(8, 4, _double, chaos="", shed_above=2)  # below one panel


def test_global_shedding_across_tenants():
    blocker, started = threading.Event(), threading.Event()

    def gated(panel):
        started.set()
        blocker.wait(timeout=30)
        return _double(panel)

    mtr = MultiTenantRuntime(chaos="", shed_above=4)
    try:
        ta = mtr.add_tenant("a", TenantSpec(8, 2, gated))
        tb = mtr.add_tenant("b", TenantSpec(8, 2, _double))
        fa = [ta.submit(np.zeros(8, np.float32)) for _ in range(2)]
        assert started.wait(timeout=30)
        fa += [ta.submit(np.zeros(8, np.float32)) for _ in range(3)]
        fb = [tb.submit(np.ones(8, np.float32))]  # 3 + 1 = budget reached
        with pytest.raises(OverloadedError, match="across all"):
            tb.submit(np.ones(8, np.float32))     # NEIGHBOR is shed too:
        assert mtr.stats["shed_requests"] == 1    # the budget is global
        assert tb.stats["shed_requests"] == 1
    finally:
        blocker.set()
    with mtr:
        mtr.flush()
        for f in fa + fb:
            f.result(timeout=60)


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_slow_launch_accounting_via_deadline():
    def sluggish(panel):
        time.sleep(0.02)
        return _double(panel)

    rt = PanelRuntime(8, 2, sluggish, chaos="",
                      resilience=ResiliencePolicy(
                          retry=None, breaker=None,
                          launch_deadline_s=0.005))
    with rt:
        futs = [rt.submit(np.ones(8, np.float32)) for _ in range(4)]
        rt.flush()
        [f.result(timeout=60) for f in futs]
    assert rt.stats["slow_launches"] == 2         # both panels over deadline
    kinds = [k for _, k, _ in rt.stats["events"]]
    assert "slow_launch" in kinds


def test_multitenant_straggler_monitor_flags_slow_tenant():
    """The pacer-retirement hook feeds real launch latencies into the
    per-tenant EWMA: a tenant whose device work is orders of magnitude
    heavier than the fleet shows up in stats()['straggler_tenants']."""
    a = jnp.asarray(np.random.RandomState(0).randn(128, 128)
                    .astype(np.float32) * 0.05)

    def heavy(panel):
        def body(_, p):
            return a @ p
        return jax.lax.fori_loop(0, 300, body, panel)

    with MultiTenantRuntime(chaos="") as mtr:
        slow = mtr.add_tenant("slow", TenantSpec(128, 2, jax.jit(heavy)))
        f1 = mtr.add_tenant("fast1", TenantSpec(128, 2, _double))
        f2 = mtr.add_tenant("fast2", TenantSpec(128, 2, _double))
        futs = []
        for t in (slow, f1, f2):
            futs += [t.submit(np.ones(128, np.float32)) for _ in range(8)]
        mtr.flush()
        [f.result(timeout=120) for f in futs]
        mtr.drain()
        stragglers = mtr.stats()["straggler_tenants"]
    assert stragglers == ["slow"]


def test_straggler_monitor():
    mon = StragglerMonitor(alpha=1.0, threshold=2.0)
    for host in ("h0", "h1", "h2", "h3"):
        mon.record(host, 1.0)
    assert mon.stragglers() == []
    assert mon.record("h3", 5.0) is True
    assert mon.stragglers() == ["h3"]
    mon.forget("h3")
    assert mon.stragglers() == []


# ---------------------------------------------------------------------------
# restart supervisor (moved here with the code)
# ---------------------------------------------------------------------------


def test_restart_supervisor_retries():
    attempts = []

    def loop():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("simulated node failure")
        return "done"

    restarts = []
    out = run_with_restarts(loop, max_restarts=5,
                            on_restart=lambda n, e: restarts.append(n))
    assert out == "done" and len(attempts) == 3 and restarts == [1, 2]


def test_restart_supervisor_gives_up():
    def loop():
        raise RuntimeError("hard failure")
    with pytest.raises(RuntimeError):
        run_with_restarts(loop, max_restarts=2)


# ---------------------------------------------------------------------------
# LaneResilience verdicts (the scheduler's decision table)
# ---------------------------------------------------------------------------


def test_lane_resilience_verdict_sequence():
    res = LaneResilience(ResiliencePolicy(
        retry=RetryPolicy(max_attempts=2, backoff_s=0.01, jitter=0.0),
        breaker=BreakerPolicy(threshold=2, cooldown_s=10.0)), "lane")
    assert res.gate(0.0) is None
    assert res.decide_failure(1.0) == "retry"     # attempt 1 of 2
    assert res.gate(1.005) == pytest.approx(1.01) # backoff gate armed
    assert res.gate(1.02) is None                 # gate expired
    assert res.decide_failure(1.02) == "fail"     # retries exhausted: panel 1
    assert res.decide_failure(2.0) == "retry"     # next panel, fresh budget
    assert res.decide_failure(2.1) == "open"      # panel 2: threshold hit
    assert res.breaker_state() == "open"
    assert not res.allow_submit(2.2)              # still cooling down
    res.on_success()
    assert res.breaker_state() == "closed" and res.allow_submit(2.2)
