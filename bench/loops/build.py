"""Closed loop of ``build_hmatrix_device_report(..., precompute=True)`` of
the configuration's design; the previous store is released before each
rebuild.  Every build's store is fingerprinted on the device, and the last
one is applied to seeded panels and compared with the reference.

Traffic keys: ``check_cols`` and ``check``: the last build is applied to
``check`` seeded panels of ``check_cols`` columns, one at a time, and the
products are compared stacked as one panel.  (One product of all the
columns at once would need another program, and at 256 columns more
memory than the chip has.)"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference

control = common.apply_control


@jax.jit
def _fingerprint(factors):
    """Sum of squares of each stored factor array: one number per array."""
    return jnp.stack([jnp.sum(a * a) for a in jax.tree_util.tree_leaves(
        factors) if jnp.issubdtype(a.dtype, jnp.floating)])


class Loop(common.Loop):
    metric = "build_s"

    def setup(self):
        self.pts = self.points()
        self.hm, _ = self.build(self.pts)
        self.shape = self.shapes(self.hm)
        jax.block_until_ready(_fingerprint(self.hm.factors))
        self.counters.update(build_plan_s=[], build_factors_s=[])
        self.prints = []

    def window(self, seconds: float) -> dict:
        n = 0
        t0 = time.perf_counter()
        while True:
            with common.span("build"):
                # dropping the last reference frees the store: it holds no
                # reference cycle, so no collection is needed (one cost
                # 60-90 ms a build on the chip)
                self.hm = None
                self.hm, rep = self.build(self.pts)
                self.prints.append(_fingerprint(self.hm.factors))
                jax.block_until_ready(self.prints[-1])
            n += 1
            self.counters["build_plan_s"].append(rep.plan_s)
            self.counters["build_factors_s"].append(rep.factors_s)
            end = time.perf_counter()
            if end - t0 >= seconds:
                break
        self.attempted = n
        return {self.metric: (end - t0) / n}

    def release(self):
        from repro import core
        t = self.traffic
        x = self.panel_pool(jax.random.fold_in(self.key, 1), t["check"],
                            t["check_cols"])
        apply = core.make_apply(self.hm)
        z = np.concatenate([np.asarray(apply(xi)) for xi in x], axis=1)
        prints = np.stack([np.asarray(p) for p in self.prints])
        self.memo = (x, z, prints)
        self.hm = self.prints = None
        gc.collect()

    def check(self):
        x, z, prints = self.memo
        differ = int(np.sum(np.any(prints != prints[-1], axis=1)))
        ref = np.concatenate([np.asarray(self.dense_apply(xi)) for xi in x],
                             axis=1)
        return [("apply_rel_err", reference.rel_err(z, ref),
                 self.limit("apply_rel_err")),
                ("builds_differing", differ, self.limit("builds_differing"))]
