"""A run with the timed path broken underneath reads ``correct`` false:
the harness's look for a chip is skipped and the library is wrapped so
that each fault the cell can have is planted where the answer is made."""
import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def result(root, cell, capsys):
    path, names = root
    rc = run.main(["--workload", names[cell], "--seed", "987654321987",
                   "--seconds", "1"], root=path, require_accelerator=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1])


def wrap_apply(monkeypatch, alter):
    from repro import core
    from repro.core import hmatrix
    make = core.make_apply

    def make_apply(hm, **kw):
        apply = make(hm, **kw)
        return lambda x: alter(apply(x))
    # the loops call core.make_apply; the serving tenants import it from
    # core.hmatrix
    monkeypatch.setattr(core, "make_apply", make_apply)
    monkeypatch.setattr(hmatrix, "make_apply", make_apply)


APPLY_FAULTS = {
    # one column of every product altered by a part in a thousand
    "answer_altered": lambda z: z.at[:, 0].multiply(1.001),
    # half of the panel left out (the first half: a served panel's
    # requests fill its first columns, the rest is padding)
    "half_panel_dropped": lambda z: z.at[:, :(z.shape[1] + 1) // 2].set(0.0),
}


@pytest.mark.parametrize("fault", sorted(APPLY_FAULTS))
def test_apply_faults(root, capsys, monkeypatch, fault):
    wrap_apply(monkeypatch, APPLY_FAULTS[fault])
    assert result(root, "paper2d.apply_r64", capsys)["correct"] is False


@pytest.mark.parametrize("fault", sorted(APPLY_FAULTS))
def test_serve_faults(root, capsys, monkeypatch, fault):
    """The fault in every launched panel: one slot altered, or half of the
    slots left out."""
    wrap_apply(monkeypatch, APPLY_FAULTS[fault])
    assert result(root, "paper2d.serve_apply", capsys)["correct"] is False


def _alter_store(hm):
    lv = max(hm.factors.levels)
    u, v = hm.factors.levels[lv]
    hm.factors.levels[lv] = (u * jnp.float32(1.01), v)


@pytest.mark.parametrize("which", ["every_build", "one_build"])
def test_build_faults(root, capsys, monkeypatch, which):
    from repro import core
    build = core.build_hmatrix_device_report
    calls = []

    def altered(*a, **kw):
        hm, rep = build(*a, **kw)
        calls.append(1)
        # call 1 is set-up's; call 2 is the window's first build
        if which == "every_build" or len(calls) == 2:
            _alter_store(hm)
        return hm, rep
    monkeypatch.setattr(core, "build_hmatrix_device_report", altered)
    res = result(root, "paper2d.build", capsys)
    assert res["correct"] is False
    if which == "one_build":
        assert res["checks"]["builds_differing"]["value"] == 1
