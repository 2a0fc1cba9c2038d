"""What every loop shares.

A traffic file names its loop (``"loop"``), found by name in
``bench/loops/<loop>.py``, and gives its parameters; the configuration
file gives the deployment.  A loop module defines ``Loop``, a subclass of
:class:`Loop` with four phases:

* ``setup()``: points, build, executors and inputs, and one call of every
  program the window will run (so nothing compiles inside it);
* ``window(seconds)``: the closed or open loop, each call inside a
  ``bench.<loop>`` host span; returns the end-to-end numbers;
* ``release()``: drops the library's state, so the reference has the chip;
* ``check()``: compares what the window produced with the plain reference
  (``reference.py``) and returns ``[(name, value, limit)]``;

and ``control(setattr_fn, cell)``, which puts the plain reference at the
next precision below the configuration's in the library's place.

The inputs come from ``--seed``; the design (the points) is fixed by the
configuration, so every seed runs the same compiled programs.  Calls into
the library go through the module attribute (``core.make_apply``, not a
name imported from it), so a test can put a broken program underneath.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, suite, work

SPAN = "bench."
CONTROL_PRECISION = "high"


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits are kept)."""
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, (seed >> 32) % 2 ** 32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for one named stream of draws from the seed."""
    return np.random.default_rng([seed % 2 ** 63, stream])


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from a seed."""

    def __init__(self, k: int, seed: int, stream: int = 0x5eed):
        self.k, self.items, self.seen = k, [], 0
        self.rng = seed_rng(seed, stream)

    def slot(self) -> int | None:
        """Count one more item of the stream; the index where it belongs
        in ``items`` (the list grows first), or None where it is not
        kept."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(self.seen))
        return j if j < self.k else None

    def offer(self, item):
        slot = self.slot()
        if slot is not None:
            self.items[slot] = item


@partial(jax.jit, static_argnames=("n", "cols", "count"))
def _normal_panels(key, *, n: int, cols: int, count: int):
    return tuple(jax.random.normal(k, (n, cols), jnp.float32)
                 for k in jax.random.split(key, count))


def design_points(cell):
    """The configuration's fixed design, made on the device by its point
    generator (``bench/points/<points>.py``)."""
    return suite.points(cell.config["points"], cell.root)(cell.config)


def span(name: str):
    return jax.profiler.TraceAnnotation(SPAN + name)


class Loop:
    """What every loop shares: the deployment's points, its build and its
    reference kernel."""

    metric: str = ""                # end-to-end metric the window yields

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.traffic, self.limits = cell.config, cell.traffic, cell.limits
        self.kernel = suite.kernel(self.cfg["kernel"], cell.root)
        self.key = seed_key(seed)
        self.counters: dict = {}
        self.attempted = self.failed = 0

    def points(self):
        return design_points(self.cell)

    def build(self, pts):
        from repro import core
        c = self.cfg
        return core.build_hmatrix_device_report(
            pts, kernel=c["kernel"], k=c["k"], c_leaf=c["c_leaf"],
            eta=c["eta"], precompute=True)

    def shapes(self, hm) -> work.Shapes:
        return work.Shapes.of_plan(hm.plan, n=self.cfg["n_points"],
                                   d=self.cfg["dim"], k=self.cfg["k"])

    def limit(self, name: str) -> float:
        return float(self.limits[name]["limit"])

    def panel_pool(self, key, count: int, cols: int):
        return list(_normal_panels(key, n=self.cfg["n_points"], cols=cols,
                                   count=count))

    def dense_apply(self, x):
        """The plain reference's product with the design, at HIGHEST."""
        return reference.dense_apply(self.pts, x, kernel=self.kernel)

    def control_window(self):
        """The control's window: as many calls as a run compares."""
        for _ in range(self.traffic["check"]):
            self.window(0.0)


def apply_control(setattr_fn, cell):
    """The reference at ``high`` in place of ``make_apply``, for loops that
    drive products."""
    from repro import core
    from repro.core import hmatrix
    kernel = suite.kernel(cell.config["kernel"], cell.root)

    def make_apply(hm, **_):
        pts = design_points(cell)

        def apply(x):
            x2 = x[:, None] if x.ndim == 1 else x
            z = reference.dense_apply(pts, x2, kernel=kernel,
                                      precision=CONTROL_PRECISION)
            return z[:, 0] if x.ndim == 1 else z
        return apply
    # the loops call core.make_apply; the serving tenants import it from
    # core.hmatrix
    setattr_fn(core, "make_apply", make_apply)
    setattr_fn(hmatrix, "make_apply", make_apply)
