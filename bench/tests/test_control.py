"""The control: the plain reference at the next precision below the
configuration's (``high``, three bfloat16 passes written out, so the CPU
rounds as the TPU does) put in the library's place under the cell's own
loop and check.  It has to come out not correct by the cell's own limit.

The products' error at ``high`` grows with N: at 2048 points it reads
3.8e-6, under the limits set at 2^19 on the chip, so the cells run at
16384 points here (1.1e-5 to 4.5e-5)."""
import pytest

from bench import calibrate, suite
from bench.tests import tiny

SIZE = {"n_points": 16384, "c_leaf": 256}
CELLS = ["paper2d.apply_r64", "paper2d.build", "paper2d.serve_apply_k80",
         "paper2d.apply_r1"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_check(tmp_path, monkeypatch, cell):
    path, names = tiny.make_root(tmp_path, SIZE)
    c = suite.load_cell(names[cell], path)
    suite.loop(c.traffic["loop"], path).control(monkeypatch.setattr, c)
    got, _ = calibrate.readings(c, seed=31337, seconds=0.0, control=True)
    limits = {k: v["limit"] for k, v in c.limits.items()}
    assert any(got[k] > limits[k] for k in got), (got, limits)
