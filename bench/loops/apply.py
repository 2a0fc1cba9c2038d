"""Closed loop of ``make_apply(hm)(X)`` over a seeded pool of (N, R)
panels, taken in order (the order goes on across windows, so the
control's one-product windows walk the pool too); each product is waited
on before the next.

Traffic keys: ``cols`` (R), ``pool`` (panels in the pool), ``check``
(products of the window compared, drawn from the seed, stacked as one
panel)."""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference

control = common.apply_control


class Loop(common.Loop):
    metric = "apply_cols_per_s"

    def setup(self):
        from repro import core
        t = self.traffic
        self.pts = self.points()
        self.hm, _ = self.build(self.pts)
        self.shape = self.shapes(self.hm)
        self.apply = core.make_apply(self.hm)
        self.pool = self.panel_pool(self.key, t["pool"], t["cols"])
        for x in self.pool[:2]:
            jax.block_until_ready(self.apply(x))
        self.sample = common.Reservoir(t["check"], self.seed)
        self.done = 0                   # products of earlier windows

    def window(self, seconds: float) -> dict:
        pool, apply, n = self.pool, self.apply, 0
        t0 = time.perf_counter()
        while True:
            i = (self.done + n) % len(pool)
            with common.span("apply"):
                z = jax.block_until_ready(apply(pool[i]))
            n += 1
            self.sample.offer((i, z))
            end = time.perf_counter()
            if end - t0 >= seconds:
                break
        self.attempted = n
        self.done += n
        return {self.metric: self.traffic["cols"] * n / (end - t0)}

    def release(self):
        self.memo = [(i, np.asarray(z)) for i, z in self.sample.items]
        del self.hm, self.apply
        gc.collect()

    def check(self):
        # the checked products stacked as one panel, and their inputs as
        # one panel of the reference: one call of a fixed width
        z = np.concatenate([z for _, z in self.memo], axis=1)
        want = np.asarray(self.dense_apply(
            jnp.concatenate([self.pool[i] for i, _ in self.memo], axis=1)))
        return [("apply_rel_err", reference.rel_err(z, want),
                 self.limit("apply_rel_err"))]
