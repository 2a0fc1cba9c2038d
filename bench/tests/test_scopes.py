"""The reduction of the program's own names in a trace (``bench/scopes.py``):
device time per scope, program spans with their arguments and the device
time inside them, three-part idle labels, and the per-layer numbers they
give; and that a trace without them reduces exactly as ``bench/trace.py``
reduces it."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import run, scopes, suite, trace, work

MS = 1_000_000
TPU = "/device:TPU:0"
DATA = os.path.join(os.path.dirname(__file__), "data")


def events():
    # window 0..100 ms; two applies; a container over the first two ops;
    # ops 10-30 and 30-40 (level 3), 60-90 (dense), 92-95 (no scope)
    ops = [("%while.1 = (s32[]", 10 * MS, 40 * MS),
           ("%fusion.1 = f32[8,64]", 10 * MS, 30 * MS),
           ("%fusion.2 = f32[8,64]", 30 * MS, 40 * MS),
           ("%fusion.3 = f32[8,64]", 60 * MS, 90 * MS),
           ("%copy.4 = f32[8,64]", 92 * MS, 95 * MS)]
    scope = ["hmatrix.apply/lowrank.L3",
             "hmatrix.apply/lowrank.L3/gather",
             "hmatrix.apply/lowrank.L3/scatter",
             "hmatrix.apply/dense/contract/bij,bjr->bir",
             ""]
    launch = [("sched", "hmatrix.serve.launch", 35 * MS, 65 * MS,
               {"tenant": "t", "requests": 3, "width": 4,
                "queued_ms_sum": 30.0, "queued_ms_max": 20.0}),
              ("sched", "hmatrix.serve.launch", 96 * MS, 97 * MS,
               {"tenant": "t", "requests": 4, "width": 4,
                "queued_ms_sum": 10.0, "queued_ms_max": 5.0}),
              ("fetch", "hmatrix.serve.fetch", 60 * MS, 70 * MS, {})]
    host = [("python", "bench.window", 0, 100 * MS),
            ("python", "bench.apply", 5 * MS, 45 * MS),
            ("python", "bench.apply", 55 * MS, 97 * MS),
            ("python", "bench.serve", 41 * MS, 59 * MS),
            ("pool", "Transpose::ExecuteChunk", 44 * MS, 50 * MS)]
    host += [p[:4] for p in launch]
    return scopes.Events(device={TPU: ops}, host=host, scopes={TPU: scope},
                         program=launch)


def read(metric, reduced):
    """What ``bench/metrics/<metric>.py`` reads from a reduction."""
    return suite.reader(metric)(SimpleNamespace(trace=reduced))


def test_scope_of_reads_the_program_path():
    assert scopes.scope_of("jit(_apply)/hmatrix.apply/permute_out/scatter:"
                           ) == "hmatrix.apply/permute_out"
    assert scopes.scope_of("jit(_level_aca)/hmatrix.build.aca.L4/aca/"
                           "jit(batched_aca)/while/body/mul") == \
        "hmatrix.build.aca.L4/aca/jit(batched_aca)/while/body"
    assert scopes.scope_of("jit(f)/mul:") == ""
    assert scopes.scope_of("") == ""


def test_reduce_synthetic_scopes_and_spans():
    r = scopes.reduce(events())
    # the container is busy time, but its body's operations carry its time
    assert r.busy_s == pytest.approx(0.063)
    assert r.scope_s("hmatrix.apply/lowrank.L3") == pytest.approx(0.030)
    assert r.scope_s("hmatrix.apply/lowrank.L3/gather") == pytest.approx(
        0.020)
    assert r.scope_s("hmatrix.apply") == pytest.approx(0.060)
    assert r.scope_s("hmatrix.apply/lowrank") == 0
    assert r.unscoped_pct() == pytest.approx(100 * 3 / 63)
    # device time inside a program span: the busy union clipped to it
    assert len(r.program["hmatrix.serve.launch"]) == 2
    assert r.program_device_ns["hmatrix.serve.launch"] == pytest.approx(
        10 * MS)                                      # 35-40 and 60-65 ms
    assert r.program["hmatrix.serve.launch"][0][2]["requests"] == 3
    assert r.program_s("hmatrix.serve.fetch") == pytest.approx(0.010)
    # the gap 40..60 ms: benchmark span, program span, host event
    assert r.idle_gaps[0] == ("bench.serve / hmatrix.serve.launch / "
                              "Transpose::ExecuteChunk", pytest.approx(0.02))
    assert r.top_ops[0] == ("%fusion.3 = f32[8,64] "
                            "[hmatrix.apply/dense/contract/bij,bjr->bir]",
                            pytest.approx(0.03))
    assert ("%copy.4 = f32[8,64]", pytest.approx(0.003)) in r.top_ops


def test_metrics_synthetic():
    r = scopes.reduce(events())
    ms, extra = read("apply_lowrank_ms", r)
    assert ms == pytest.approx(15.0)                  # 30 ms over 2 applies
    assert extra == {"L3": pytest.approx(15.0), "gather": pytest.approx(10.0),
                     "contract": 0, "scatter": pytest.approx(5.0)}
    ms, extra = read("apply_dense_ms", r)
    assert ms == pytest.approx(15.0)
    assert extra["contract"] == pytest.approx(15.0) and extra["kernel"] == 0
    assert read("apply_permute_ms", r) is None
    assert read("serve_queue_ms", r) == (pytest.approx(40 / 7),
                                        {"max": 20.0})
    assert read("serve_pad_pct", r) == (pytest.approx(12.5),
                                       {"launches": 2, "mean_width": 4.0})
    assert read("serve_fetch_ms", r) == pytest.approx(10.0)
    assert read("build_aca_dev_s", r) is None
    assert read("build_fetch_s", r) is None
    rep = scopes.report(r)
    assert set(rep["metrics"]) == {"apply_lowrank_ms", "apply_dense_ms",
                                   "serve_queue_ms", "serve_pad_pct",
                                   "serve_fetch_ms"}
    assert rep["metrics"]["serve_queue_ms"]["max"] == 20.0


def test_build_metrics_synthetic():
    ops = [("%fusion.1 = f32[2]", 10 * MS, 20 * MS),
           ("%fusion.2 = f32[2]", 20 * MS, 50 * MS),
           ("%fusion.3 = f32[2]", 50 * MS, 90 * MS)]
    scope = ["hmatrix.build.plan/morton_sort",
             "hmatrix.build.aca.L10/aca/jit(batched_aca)",
             "hmatrix.build.aca.L9/gather"]
    fetch = ("python", "hmatrix.build.fetch", 15 * MS, 25 * MS, {})
    ev = scopes.Events(device={TPU: ops},
                       host=[("python", "bench.window", 0, 100 * MS),
                             ("python", "bench.build", 5 * MS, 95 * MS),
                             fetch[:4]],
                       scopes={TPU: scope}, program=[fetch])
    r = scopes.reduce(ev)
    s, extra = read("build_aca_dev_s", r)
    assert s == pytest.approx(0.07)
    assert list(extra) == ["L9", "L10"]               # in level order
    assert extra["L10"] == pytest.approx(0.03)
    s, extra = read("build_fetch_s", r)
    assert s == pytest.approx(0.010)
    assert extra == {"device_s": pytest.approx(0.010)}


def _old():
    with open(os.path.join(DATA, "apply_trace.json")) as f:
        return json.load(f)


def test_trace_without_program_names_reduces_as_before():
    obj = _old()
    want = trace.reduce(trace.Events.from_json(obj))
    got = scopes.reduce(scopes.Events.from_json(obj))
    for name in ("window", "chips", "busy_ns", "spans", "span_device_ns",
                 "top_ops", "idle_gaps"):
        assert getattr(got, name) == getattr(want, name), name
    assert got.program == {} and got.unscoped_pct() == 100.0
    assert scopes.report(got)["metrics"] == {}
    # the accepted readers read the same numbers from either reduction
    shapes = work.Shapes(n=1 << 18, n_pad=1 << 18, d=2, k=16, c_leaf=256,
                         aca_blocks={3: 4}, dense_blocks=22746)
    for name in ("apply_dev_ms", "idle_pct.apply", "apply_roofline_pct"):
        reads = [suite.reader(name)(run.Run(
            cell="paper2d.apply_r64", device_kind="TPU v5 lite", cols=64,
            shapes=shapes, counters={}, trace=r)) for r in (want, got)]
        assert reads[0] == reads[1], name


# One R = 64 apply of the paper2d design (N = 2^19, leaves of 2048) on one
# TPU v5 lite, with the apply's scopes: the device operations of one
# bench.apply span, the host events around it, inside a bench.window that
# runs from the end of the apply before to the start of the apply after.
RECORDED = os.path.join(DATA, "apply_scoped_trace.json")
LEVELS = ["L3", "L4", "L5", "L6", "L7", "L8"]


def recorded():
    with open(RECORDED) as f:
        return scopes.Events.from_json(json.load(f))


def test_readers_on_recorded_scoped_trace():
    r = scopes.reduce(recorded())
    assert r.chips == 1 and len(r.spans["bench.apply"]) == 1
    rec = run.Run(cell="paper2d.apply_r64", device_kind="TPU v5 lite",
                  cols=64, shapes=None, counters={}, trace=r)
    dev_ms = suite.reader("apply_dev_ms")(rec)
    assert dev_ms == pytest.approx(332.850519, rel=1e-6)
    lowrank, per = read("apply_lowrank_ms", r)
    dense, parts = read("apply_dense_ms", r)
    permute, each = read("apply_permute_ms", r)
    assert lowrank == pytest.approx(109.939127, rel=1e-6)
    assert [k for k in per if k.startswith("L")] == LEVELS
    assert sum(per[k] for k in LEVELS) == pytest.approx(lowrank)
    assert per["gather"] + per["contract"] + per["scatter"] <= lowrank
    assert dense == pytest.approx(113.047, rel=1e-6)
    assert parts["contract"] > 0.8 * dense
    assert each["permute_out"] == pytest.approx(97.577986, rel=1e-6)
    assert 95 <= 100 * (lowrank + dense + permute) / dev_ms <= 100.5
    assert r.unscoped_pct() < 3
    # the result fusion is the permutation back; the dense leaves' fusion
    # is their contraction (a fusion carries its root's scope)
    names = [n for n, _ in r.top_ops]
    assert names[0] == "%fusion.41 = f32[524288,64] [hmatrix.apply/permute_out]"
    assert names[1].startswith("%fusion.44 = f32[3974,2048,64] "
                               "[hmatrix.apply/dense/contract/")
    # the readers of other loops find nothing to read in an apply trace
    for name in ("build_aca_dev_s", "build_fetch_s", "serve_queue_ms",
                 "serve_pad_pct", "serve_fetch_ms"):
        assert read(name, r) is None, name


def test_new_readers_find_nothing_in_the_harness_reduction():
    # bench/run.py reduces with trace.reduce, which keeps no program names
    plain = trace.reduce(recorded())
    for name in scopes.METRICS:
        assert read(name, plain) is None, name


# -- the trace file as protobuf wire format -------------------------------


def _key(num, kind):
    return _varint(num << 3 | kind)


def _varint(n):
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _key(num, 0) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _key(num, 2) + _varint(len(value)) + value
    return out


def test_op_scopes_read_from_the_trace_file():
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    copy = "%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)"
    stat = lambda mid, **v: _msg((1, mid), *[(5 if k == "s" else 7, x)
                                             for k, x in v.items()])
    meta = [(1, _msg((1, 1), (2, fusion), (5, stat(7, s="jit(f)/hmatrix."
                                                   "apply/dense/gather/"
                                                   "gather:")))),
            (2, _msg((1, 2), (2, copy), (5, stat(7, r=9))))]
    device = _msg(
        (1, 3), (2, TPU),
        (3, _msg((2, "XLA Modules"), (4, _msg((1, 1))))),
        (3, _msg((2, trace.OPS_LINE), (4, _msg((1, 1), (2, 5))),
                 (4, _msg((1, 2))), (4, _msg((1, 1))))),
        *[(4, _msg((1, k), (2, m))) for k, m in meta],
        (5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op"))))),
        (5, _msg((1, 9), (2, _msg((1, 9), (2, "jit(f)/hmatrix.apply/"
                                                 "permute_in/copy:"))))))
    host = _msg((1, 4), (2, "/host:CPU"), (3, _msg((2, "python"))))
    space = memoryview(_msg((1, host), (1, device), (4, "machine")))
    names, got = scopes._op_scopes(space)[TPU]
    assert names == [fusion, copy, fusion]
    assert got == ["hmatrix.apply/dense/gather", "hmatrix.apply/permute_in",
                   "hmatrix.apply/dense/gather"]
    assert list(scopes._op_scopes(space)) == [TPU]


def test_load_keeps_program_span_arguments(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("hmatrix.serve.launch",
                                              tenant="t", requests=3,
                                              width=4, queued_ms_sum=1.5,
                                              queued_ms_max=1.0):
                f(jnp.ones(4)).block_until_ready()
    ev = scopes.load(str(tmp_path))
    assert ev.host == trace.load(str(tmp_path)).host
    [(_, name, s, e, args)] = ev.program
    assert name == "hmatrix.serve.launch" and e > s
    assert args == {"tenant": "t", "requests": 3, "width": 4,
                    "queued_ms_sum": 1.5, "queued_ms_max": 1.0}
