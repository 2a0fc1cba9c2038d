"""Work counts from the plan's shapes, tied to the store the build made."""
import pytest

from bench import suite, work


@pytest.fixture(scope="module")
def built():
    from repro import core
    pts = suite.points("halton")({"n_points": 2048, "dim": 2, "side": 1.0})
    hm, _ = core.build_hmatrix_device_report(pts, k=16, c_leaf=64,
                                             precompute=True)
    return hm


def test_store_bytes_match_the_factor_store(built):
    s = work.Shapes.of_plan(built.plan, n=2048, d=2, k=16)
    assert s.store_bytes() == built.factors.nbytes()["low_rank"]


def test_apply_work(built):
    s = work.Shapes.of_plan(built.plan, n=2048, d=2, k=16)
    r = 8
    a = work.apply_work(s, r)
    assert a.bytes == s.store_bytes() + s.n_pad * 2 * 4 + 2 * 2048 * r * 4
    lowrank = sum(4 * b * (s.n_pad >> lv) * 16 * r
                  for lv, b in s.aca_blocks.items())
    assert a.flops == lowrank + 2 * s.dense_blocks * 64 ** 2 * r


def test_roofline_share_and_bound():
    w = work.Work(flops=197e12 * 1e-3, bytes=819e9 * 2e-3)
    pct, bound = work.roofline_pct(w, 4e-3, "TPU v5 lite")
    assert bound == "memory" and pct == pytest.approx(50.0)
    assert work.roofline_pct(w, 0.0, "TPU v5 lite") is None
    with pytest.raises(ValueError):
        work.peaks("cpu")
