"""The paper's 3-D Matern deployment (``paper3d_matern``): its cell loads,
its plain reference kernel is pinned against scipy, a tiny copy of the cell
runs ``correct`` end to end on the CPU, and the control at ``high`` fails
the cell's check."""
import ast
import json

import numpy as np
import pytest
import scipy.special

from bench import calibrate, run, suite
from bench.tests import tiny

CELL = "paper3d_matern.apply_r64"


def test_cell_loads():
    c = suite.load_cell(CELL)
    assert c.chips == 1
    assert c.traffic == suite.load_cell("paper2d.apply_r64").traffic
    cfg = c.config
    assert (cfg["n_points"], cfg["dim"], cfg["kernel"], cfg["points"]) == (
        2 ** 19, 3, "matern", "halton")
    assert (cfg["eta"], cfg["k"], cfg["c_leaf"]) == (1.5, 16, 2048)
    assert set(cfg["reduced"]) == {"n_points", "dtype"}
    assert {m["name"] for m in c.end_to_end} == {"apply_cols_per_s",
                                                  "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "apply_dev_ms", "apply_roofline_pct", "idle_pct.apply"}
    lim = c.limits["apply_rel_err"]
    assert lim["lower"] < lim["limit"] < lim["upper"]


def test_reference_kernel_imports_nothing_of_the_library():
    with open(f"{suite.BENCH_DIR}/kernels/matern.py") as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not any(n.split(".")[0] in ("repro", "bench")
                             for n in names), names


def test_reference_k1_matches_scipy():
    """r K_1(r) in float32 within 1e-6 of scipy's, relative, on
    [1e-4, 40] (it reads about 2.1e-7)."""
    import jax.numpy as jnp
    m = suite.module("kernels", "matern")
    r = np.logspace(-4, np.log10(40.0), 20001).astype(np.float32)
    got = np.asarray(m.r_k1(jnp.asarray(r)), np.float64)
    want = r.astype(np.float64) * scipy.special.k1(r.astype(np.float64))
    assert np.max(np.abs(got / want - 1.0)) <= 1e-6
    assert np.asarray(m.r_k1(jnp.zeros(2, jnp.float32))).tolist() == [1, 1]


def test_reference_chebyshev_coefficients_are_the_stated_fit():
    from numpy.polynomial import chebyshev
    m = suite.module("kernels", "matern")

    def g(t):
        r = 2.0 / (t + 1.0)
        return np.sqrt(r) * scipy.special.k1e(r)
    fit = chebyshev.chebinterpolate(g, len(m.CHEB) - 1)
    assert np.allclose(fit, m.CHEB, rtol=0, atol=1e-12)


@pytest.mark.parametrize("d", [2, 3])
def test_reference_kernel_values(d):
    """phi(0) is 1 / (2^(beta-1) Gamma(beta)) exactly; off the diagonal the
    kernel is scipy's r K_1(r) times it."""
    import jax.numpy as jnp
    m = suite.module("kernels", "matern")
    pts = np.random.default_rng(d).random((64, d)).astype(np.float32)
    got = np.asarray(m.kernel(jnp.asarray(pts), jnp.asarray(pts)),
                     np.float64)
    beta = d / 2 + 1
    norm = 2 ** (beta - 1) * scipy.special.gamma(beta)
    assert np.all(np.diag(got) == np.float32(1 / norm))
    p = pts.astype(np.float64)
    r = np.sqrt(((p[:, None] - p[None]) ** 2).sum(-1))
    off = ~np.eye(64, dtype=bool)
    want = r[off] * scipy.special.k1(r[off]) / norm
    assert np.max(np.abs(got[off] / want - 1)) < 1e-6


def test_tiny_cell_runs_correct(tmp_path, capsys):
    path, names = tiny.make_root(tmp_path)
    rc = run.main(["--workload", names[CELL], "--seed", str(2 ** 33 + 17),
                   "--seconds", "1", "--trace", "0"], root=path,
                  require_accelerator=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"apply_cols_per_s", "setup_s"}


def test_control_fails_the_check(tmp_path, monkeypatch):
    """At 16384 points the control reads 1.18e-5 and the library 3.7e-6
    on the CPU (PERF.md, section 2)."""
    path, names = tiny.make_root(tmp_path, {"n_points": 16384,
                                            "c_leaf": 256})
    c = suite.load_cell(names[CELL], path)
    suite.loop(c.traffic["loop"], path).control(monkeypatch.setattr, c)
    got, _ = calibrate.readings(c, seed=31337, seconds=0.0, control=True)
    assert got["apply_rel_err"] > c.limits["apply_rel_err"]["limit"], got
