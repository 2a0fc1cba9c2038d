"""The program's names in a profiler trace: device scopes in the apply's and
the build's operation metadata, and host spans (with arguments) around the
build's stages and the serving runtime's pack, launch, pacing and fetch.

Scopes are metadata only, so the scoped programs must compute bit for bit
what the same code computes without them."""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_hmatrix, build_hmatrix_device_report,
                        compute_factors_device, halton, make_apply)
from repro.core import build_device, hmatrix
from repro.kernels.batched_aca.ref import batched_aca_level_ref
from repro.serve.tenancy import MultiTenantRuntime, apply_tenant

N, C_LEAF, K = 1500, 64, 8


@pytest.fixture(scope="module")
def points():
    return jnp.asarray(halton(N, 2))


@pytest.fixture(scope="module")
def panel():
    return jax.random.normal(jax.random.PRNGKey(3), (N, 8), jnp.float32)


def _instructions(hlo: str) -> list:
    """The compiled program's instructions without metadata or numbering."""
    out = []
    for line in hlo.splitlines():
        if " = " not in line or line.lstrip().startswith(("FileNames",
                                                          "FunctionNames")):
            continue
        line = re.sub(r",? metadata=\{[^}]*\}", "", line)
        out.append(re.sub(r"\.\d+\b", "", line))
    return out


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        yield


def _host_events(tdir: str, prefix: str) -> list:
    """(name, args) of every host event in the trace whose name starts with
    ``prefix``, in start order."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                found += [(e.start_ns, e.name, {k: v for k, v in e.stats})
                          for e in line.events if e.name.startswith(prefix)]
    return [(name, args) for _, name, args in sorted(found,
                                                     key=lambda f: f[0])]


@pytest.mark.parametrize("precompute", [True, False], ids=["P", "NP"])
def test_apply_program_carries_scopes(points, panel, precompute):
    hm = build_hmatrix(points, k=K, c_leaf=C_LEAF, precompute=precompute)
    text = make_apply(hm).lower(panel).as_text(debug_info=True)
    want = ["hmatrix.apply/permute_in", "hmatrix.apply/permute_out"]
    want += [f"hmatrix.apply/dense/{p}"
             for p in ("gather", "kernel", "contract", "scatter")]
    parts = ("gather", "contract", "scatter") + (() if precompute
                                                 else ("aca",))
    assert len(hm.plan.aca_levels) >= 2
    want += [f"hmatrix.apply/lowrank.L{level}/{p}"
             for level in hm.plan.aca_levels for p in parts]
    missing = [w for w in want if w + "/" not in text]
    assert not missing, missing


@pytest.mark.parametrize("precompute", [True, False], ids=["P", "NP"])
def test_scopes_leave_apply_program_and_result_unchanged(points, panel,
                                                         precompute,
                                                         monkeypatch):
    hm = build_hmatrix(points, k=K, c_leaf=C_LEAF, precompute=precompute)
    scoped = make_apply(hm)
    z = np.asarray(scoped(panel))
    program = _instructions(scoped.lower(panel).compile().as_text())
    with _no_scopes(monkeypatch):
        plain = make_apply(hm)
        z0 = np.asarray(plain(panel))
        program0 = _instructions(plain.lower(panel).compile().as_text())
    assert np.array_equal(z, z0)
    assert program == program0


def _scope_primitives(text: str, scope: str) -> set:
    """The primitives whose locations in the lowered program lie under
    ``scope``."""
    return set(re.findall(r'loc\("[^"]*' + re.escape(scope) + r'/([a-z_]+)"',
                          text))


def _scatter_back(tree, z_pad):
    """The permutation back as a row scatter by ``perm``."""
    z = jnp.zeros((tree.n,) + z_pad.shape[1:], z_pad.dtype)
    return z.at[tree.perm].set(z_pad[: tree.n])


@pytest.mark.parametrize("precompute", [True, False], ids=["P", "NP"])
def test_apply_permutes_back_by_gather(points, panel, precompute,
                                       monkeypatch):
    hm = build_hmatrix(points, k=K, c_leaf=C_LEAF, precompute=precompute)
    gathered = make_apply(hm)
    ops = _scope_primitives(gathered.lower(panel).as_text(debug_info=True),
                            "hmatrix.apply/permute_out")
    assert "gather" in ops and not any("scatter" in p for p in ops), ops
    with monkeypatch.context() as m:
        m.setattr(hmatrix, "permute_from_tree", _scatter_back)
        scattered = make_apply(hm)
        old = _scope_primitives(
            scattered.lower(panel).as_text(debug_info=True),
            "hmatrix.apply/permute_out")
        assert "scatter" in old, old
        z0 = np.asarray(scattered(panel))
    assert np.array_equal(np.asarray(gathered(panel)), z0)


def test_build_plan_and_aca_ops_carry_scopes(points):
    hm, _ = build_hmatrix_device_report(points, k=K, c_leaf=C_LEAF,
                                        precompute=True)
    plan, tree = hm.plan, hm.tree
    text = build_device._plan_program.lower(
        points, n_pad=plan.n_pad, n_levels=plan.n_levels,
        eta=float(plan.eta)).as_text(debug_info=True)
    for stage in ("morton_sort", "bbox", "blocktree"):
        assert f"hmatrix.build.plan/{stage}/" in text, stage
    assert len(plan.aca_levels) >= 2
    for level, blocks in plan.aca_levels.items():
        rows, cols = jnp.asarray(blocks[:, 0]), jnp.asarray(blocks[:, 1])
        gather = build_device._level_points.lower(
            tree.points, rows, cols, level=level)
        assert f"hmatrix.build.aca.L{level}/gather/" in gather.as_text(
            debug_info=True)
        rp, cp = build_device._level_points(tree.points, rows, cols,
                                            level=level)
        aca = build_device._level_aca.lower(rp, cp, level=level,
                                            kernel=hm.kernel, k=K)
        assert f"hmatrix.build.aca.L{level}/aca/" in aca.as_text(
            debug_info=True)
        # every compiled operation of the launch is under the level's scope
        # (bare names are the bodies of reductions, not operations)
        names = [n for n in re.findall(r'op_name="([^"]*)"',
                                       aca.compile().as_text()) if "/" in n]
        assert names and all(f"/hmatrix.build.aca.L{level}/aca/" in n
                             for n in names), names


def test_build_factors_bit_identical_to_eager_launches(points):
    hm, _ = build_hmatrix_device_report(points, k=K, c_leaf=C_LEAF)
    got = compute_factors_device(hm.tree, hm.plan, "gaussian", K)
    for level, blocks in hm.plan.aca_levels.items():
        want = batched_aca_level_ref(hm.tree.points,
                                     jnp.asarray(blocks[:, 0]),
                                     jnp.asarray(blocks[:, 1]), level,
                                     "gaussian", K)
        for a, b in zip(got[level], want):
            assert np.array_equal(np.asarray(a), np.asarray(b)), level


def test_build_writes_host_spans(points, tmp_path):
    build_hmatrix_device_report(points, k=K, c_leaf=C_LEAF, precompute=True)
    with jax.profiler.trace(str(tmp_path)):
        hm, _ = build_hmatrix_device_report(points, k=K, c_leaf=C_LEAF,
                                            precompute=True)
    names = [n for n, _ in _host_events(str(tmp_path), "hmatrix.build.")]
    # the larger level groups (blocks x rows) are launched first
    plan = hm.plan
    dispatch = [f"hmatrix.build.dispatch.L{level}" for level in sorted(
        plan.aca_levels, key=lambda lv: (plan.aca_levels[lv].shape[0]
                                         * (plan.n_pad >> lv)),
        reverse=True)]
    assert names == (["hmatrix.build.plan", "hmatrix.build.fetch",
                      "hmatrix.build.factors"] + dispatch
                     + ["hmatrix.build.wait", "hmatrix.build.store"])


@pytest.mark.parametrize("dim,kernel", [(2, "gaussian"), (3, "matern")])
def test_build_plan_span_carries_the_near_field(tmp_path, dim, kernel):
    pts = jnp.asarray(halton(N, dim))
    build_hmatrix_device_report(pts, kernel=kernel, k=K, c_leaf=C_LEAF)
    with jax.profiler.trace(str(tmp_path)):
        _, report = build_hmatrix_device_report(pts, kernel=kernel, k=K,
                                                c_leaf=C_LEAF)
    plan = [a for n, a in _host_events(str(tmp_path), "hmatrix.build.")
            if n == "hmatrix.build.plan"]
    assert report.num_dense_blocks > 0
    assert report.near_field_entries == report.num_dense_blocks * C_LEAF ** 2
    # the Matérn's K_1 branches at r = 2, which no pair in [0,1]^d reaches:
    # every near-field block skips the far branch; the Gaussian has none
    pruned = report.num_dense_blocks if kernel == "matern" else 0
    assert report.near_field_pruned_blocks == pruned
    assert plan == [{"dim": dim, "kernel": kernel,
                     "near_field_blocks": report.num_dense_blocks,
                     "near_field_entries": report.near_field_entries,
                     "near_field_pruned_blocks": pruned}]


def test_serving_writes_launch_and_fetch_spans(points, tmp_path):
    hm = build_hmatrix(points, k=K, c_leaf=C_LEAF, precompute=True)
    vecs = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (37, N)))
    with MultiTenantRuntime(max_inflight=2) as mtr:
        tenant = mtr.add_tenant("t0", apply_tenant(hm, max_batch=8,
                                                   deadline_s=0.005))
        mtr.precompile()
        with jax.profiler.trace(str(tmp_path)):
            futs = [tenant.submit(v) for v in vecs]
            mtr.flush()
            for f in futs:
                f.result(timeout=120)
    events = _host_events(str(tmp_path), "hmatrix.serve.")
    launches = [a for n, a in events if n == "hmatrix.serve.launch"]
    assert launches
    assert sum(a["requests"] for a in launches) == len(vecs)
    for a in launches:
        assert a["tenant"] == "t0"
        assert a["width"] >= a["requests"] >= 1
        assert a["queued_ms_max"] >= 0
        assert a["queued_ms_sum"] >= a["queued_ms_max"]
    names = [n for n, _ in events]
    assert names.count("hmatrix.serve.pack") == len(launches)
    assert 1 <= names.count("hmatrix.serve.fetch") <= len(launches)
