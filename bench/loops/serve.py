"""One apply tenant in ``MultiTenantRuntime``: single (N,) vectors from a
seeded pool arrive open-loop, and each request's latency runs from its
due time to its result on the host.

Traffic keys: ``arrivals`` (the arrival process, ``bench/arrivals/<name>.py``,
with its own keys such as ``rate_per_s``), ``max_batch``, ``deadline_s``,
``max_queue``, ``max_inflight`` (the tenant and runtime settings), ``pool``
(vectors in the pool), ``check`` (requests of the window compared, drawn
from the seed) and ``check_partial`` (further requests compared, drawn
from those served in panels launched less than full)."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from bench import common, reference, suite

control = common.apply_control


class Loop(common.Loop):
    metric = "serve_p95_ms"

    def setup(self):
        from repro.serve import tenancy
        t = self.traffic
        self.pts = self.points()
        self.hm, _ = self.build(self.pts)
        self.shape = self.shapes(self.hm)
        self.mtr = tenancy.MultiTenantRuntime(max_inflight=t["max_inflight"])
        self.tenant = self.mtr.add_tenant("apply", tenancy.apply_tenant(
            self.hm, max_batch=t["max_batch"], deadline_s=t["deadline_s"],
            max_queue=t["max_queue"]))
        self.mtr.precompile()
        pool = np.asarray(self.panel_pool(self.key, 1, t["pool"])[0])
        self.vecs = [np.ascontiguousarray(pool[:, j]) for j in range(t["pool"])]
        for f in [self.tenant.submit(v) for v in self.vecs[:t["max_batch"]]]:
            f.result(timeout=600)
        self.sample = common.Reservoir(t["check"], self.seed)
        self.partial = common.Reservoir(t["check_partial"], self.seed, 0xba7c)
        self.counters["launched_widths"] = []

    def window(self, seconds: float, traffic: dict | None = None) -> dict:
        t = self.traffic if traffic is None else traffic
        gaps = suite.arrivals(t["arrivals"], self.cell.root)(t, seconds,
                                                            self.seed)
        due = np.cumsum(gaps)
        futures, lag = [None] * len(due), []
        finished = np.full(len(due), np.nan)     # only for served requests
        ready = threading.Semaphore(0)
        n_vec, full = len(self.vecs), self.traffic["max_batch"]

        def keep(reservoir, k, res):
            slot = reservoir.slot()
            if slot is not None:
                reservoir.items[slot] = (k % n_vec, np.array(res))

        def collect():
            # A panel's requests are consecutive (the tenant's queue is
            # FIFO) and their results are columns of one fetched panel: a
            # new base array starts a new panel.  Only the sampled results
            # are kept, as copies, so the fetched panels are freed.
            base, members = None, []

            def close():
                if len(members) < full:
                    for k, res in members:
                        keep(self.partial, k, res)
                members.clear()

            for k in range(len(due)):
                ready.acquire()
                f, futures[k] = futures[k], None
                try:
                    res = None if f is None else f.result(timeout=seconds + 60)
                except Exception:           # refused, failed or never came
                    res = None
                if res is None:
                    continue
                finished[k] = time.perf_counter()
                owner = res if res.base is None else res.base
                if owner is not base:
                    close()
                    base = owner
                members.append((k, res))
                keep(self.sample, k, res)
            close()

        collector = threading.Thread(target=collect, daemon=True)
        collector.start()
        t0 = time.perf_counter()
        for k, d in enumerate(due):
            pause = t0 + d - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            lag.append(time.perf_counter() - t0 - d)
            with common.span("serve"):
                try:
                    futures[k] = self.tenant.submit(self.vecs[k % n_vec])
                except Exception:           # refused at admission
                    futures[k] = None
            ready.release()
        collector.join(timeout=seconds + 120)
        served = ~np.isnan(finished)
        lat = finished[served] - t0 - due[served]
        self.attempted = len(due)
        self.failed += len(due) - int(served.sum())
        self.counters["in_partial_panels"] = self.partial.seen
        self.counters["launched_widths"] = list(
            self.tenant.stats()["launched_widths"])
        self.counters["generator_lag_p95_s"] = float(np.percentile(lag, 95))
        if not lat.size:
            raise RuntimeError("no request of the window completed")
        return {self.metric: 1e3 * float(np.percentile(lat, 95))}

    def control_window(self):
        t = self.traffic
        self.window((t["check"] + t["check_partial"]) / t["rate_per_s"])

    def release(self):
        self.memo = list(self.sample.items) + list(self.partial.items)
        self.mtr.close()
        del self.hm, self.mtr, self.tenant
        gc.collect()

    def check(self):
        import jax.numpy as jnp
        # the reference of each pool vector that a checked request carried;
        # the results are compared stacked, as a panel
        pool = sorted({i for i, _ in self.memo})
        col = {i: c for c, i in enumerate(pool)}
        x = jnp.asarray(np.stack([self.vecs[i] for i in pool], 1))
        ref = np.asarray(self.dense_apply(x))
        ref = ref[:, [col[i] for i, _ in self.memo]]
        z = np.stack([r for _, r in self.memo], 1)
        return [("serve_rel_err", reference.rel_err(z, ref),
                 self.limit("serve_rel_err"))]
