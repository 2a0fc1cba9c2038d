"""The reduction from trace events to busy time, idle share, device time
per span and idle gaps."""
import json
import os

import pytest

from bench import trace

MS = 1_000_000


def events():
    # window 0..100 ms; two apply spans; ops 10-30, 25-40 (overlap), 60-90
    return trace.Events(
        device={"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                                  ("fusion.2", 25 * MS, 40 * MS),
                                  ("dot.3", 60 * MS, 90 * MS)]},
        host=[("python", "bench.window", 0, 100 * MS),
              ("python", "bench.apply", 5 * MS, 45 * MS),
              ("python", "bench.apply", 55 * MS, 95 * MS),
              ("python", "PjitFunction(_apply)", 44 * MS, 56 * MS)])


def test_merge_and_covered():
    merged = trace.merge([(5, 9), (1, 3), (2, 4), (9, 12)])
    assert merged == [(1, 4), (5, 12)]
    assert trace.covered(merged, 3, 6) == 2
    assert trace.covered(merged, 0, 100) == 10


def test_reduce_synthetic():
    r = trace.reduce(events())
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.06)
    assert r.idle_pct() == pytest.approx(40.0)
    assert r.device_s_per_span("bench.apply") == pytest.approx(0.03)
    assert r.top_ops[0] == ("dot.3", pytest.approx(0.03))
    label, secs = r.idle_gaps[0]
    assert secs == pytest.approx(0.02)            # 40..60 ms
    assert label == "PjitFunction(_apply)"
    assert r.idle_gaps[1][0].startswith("bench.apply")


def test_reduce_needs_window_and_device():
    ev = events()
    ev.host = [h for h in ev.host if h[1] != trace.WINDOW]
    with pytest.raises(ValueError):
        trace.reduce(ev)
    with pytest.raises(ValueError):
        trace.reduce(trace.Events(device={}, host=events().host))


# A trace recorded on one TPU v5 lite: four R = 64 applies of the paper's
# design at N = 2^18 in leaves of 256 inside one bench.window, each in
# bench.apply.
RECORDED = os.path.join(os.path.dirname(__file__), "data", "apply_trace.json")
PAPER2D_BLOCKS = {3: 4, 4: 98, 5: 136, 6: 842, 7: 1238, 8: 4558, 9: 7886,
                  10: 20366}


def recorded():
    with open(RECORDED) as f:
        return trace.Events.from_json(json.load(f))


def test_reduce_recorded_tpu_trace():
    r = trace.reduce(recorded())
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.508988357)
    assert r.busy_s == pytest.approx(0.500044694)
    assert r.idle_pct() == pytest.approx(1.757144908, rel=1e-6)
    assert len(r.spans["bench.apply"]) == 4
    assert r.device_s_per_span("bench.apply") == pytest.approx(0.12476511,
                                                                rel=1e-6)
    assert r.top_ops[0][0] == "%fusion.34 = f32[22746,256,64]"
    assert len(r.top_ops) == 10 and len(r.idle_gaps) == 10
    # device time inside the spans is at most the busy time of the window
    assert r.span_device_ns["bench.apply"] <= r.busy_ns


def test_readers_on_recorded_trace():
    from bench import run, suite, work
    shapes = work.Shapes(n=1 << 18, n_pad=1 << 18, d=2, k=16, c_leaf=256,
                         aca_blocks=PAPER2D_BLOCKS, dense_blocks=22746)
    rec = run.Run(cell="paper2d.apply_r64", device_kind="TPU v5 lite",
                  cols=64, shapes=shapes, counters={},
                  trace=trace.reduce(recorded()))
    assert suite.reader("apply_dev_ms")(rec) == pytest.approx(124.76511,
                                                              rel=1e-6)
    assert suite.reader("idle_pct.apply")(rec) == pytest.approx(1.757144908,
                                                                rel=1e-6)
    pct, extra = suite.reader("apply_roofline_pct")(rec)
    least = work.apply_work(shapes, 64).bytes / 819e9
    assert extra == {"bound": "memory"}
    assert pct == pytest.approx(100 * least / 0.12476511, rel=1e-6)
    assert 2.5 < pct < 3.5
    # the readers of other loops find nothing to read in an apply trace
    assert suite.reader("idle_pct.build")(rec) is None
    assert suite.reader("serve_panel_cols")(rec) is None
