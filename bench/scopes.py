#!/usr/bin/env python3
"""The program's own names in a profiler trace, reduced beside
``bench/trace.py``'s numbers.

The library names its work (``docs/ARCHITECTURE.md``, "Tracing"): each
device operation carries a scope path in its metadata
(``hmatrix.apply/lowrank.L5/scatter``), and the host spans it opens are
named ``hmatrix.*``, some with arguments (``hmatrix.serve.launch``:
``requests``, ``width``, ...).  :func:`load` reads the events
:func:`trace.load` reads, with each device operation's scope and each
program span's arguments besides; :func:`reduce` computes
:class:`trace.Reduced`'s numbers as :func:`trace.reduce` does, and adds

* device time per scope (the operations' own time, chip mean);
* the program's spans with their arguments, and the device time inside
  each, as for the benchmark's ``bench.`` spans;
* the share of device time under no program scope;
* idle-gap labels with the innermost program span between the benchmark
  span and the host event, and operation names with their scope in
  brackets.

Where a trace holds no program scope or span, every number, label and
name is :func:`trace.reduce`'s.

A TPU trace keeps an operation's scope in the ``tf_op`` stat of the
operation's event metadata, which ``jax.profiler.ProfileData`` does not
expose; :func:`_op_scopes` reads it from the trace file itself.

The per-layer numbers these names give are read by
``bench/metrics/<name>.py`` for each name in :data:`METRICS`, from this
reduction only (:func:`scoped`).  ``bench/run.py`` reduces its trace with
:func:`trace.reduce`, so no benchmark metric reads them yet; this file,
run as a script, runs a cell as ``bench/run.py`` does with this
reduction in its place, and prints them after the result line::

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> --trace 1
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from types import SimpleNamespace

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "hmatrix."
# trace.reduce itself: main() puts reduce() in its place while a cell runs
_trace_reduce = trace.reduce


@dataclass
class Events(trace.Events):
    """:class:`trace.Events`, with ``scopes``: chip -> the scope path of
    each of ``device[chip]``'s operations ("" where it has none), and
    ``program``: [(thread, name, start, end, args)] of the program's host
    spans (also in ``host``)."""
    scopes: dict = field(default_factory=dict)
    program: list = field(default_factory=list)

    @classmethod
    def from_json(cls, obj: dict) -> "Events":
        base = trace.Events.from_json(obj)
        return cls(device=base.device, host=base.host,
                   scopes={k: list(v) for k, v in obj.get("scopes",
                                                          {}).items()},
                   program=[(t, n, s, e, dict(a))
                            for t, n, s, e, a in obj.get("program", [])])


def load(trace_dir: str) -> Events:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = paths[-1]
    data = ProfileData.from_file(path)
    ev = Events()
    for plane in data.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops += [(trace._short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            ev.device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    span = (line.name, e.name, e.start_ns,
                            e.start_ns + e.duration_ns)
                    ev.host.append(span)
                    if e.name.startswith(PROGRAM_PREFIX):
                        ev.program.append(span + ({k: v for k, v in
                                                   e.stats},))
    with open(path, "rb") as f:
        found = _op_scopes(memoryview(f.read()))
    for chip, ops in ev.device.items():
        names, scopes = found.get(chip, ([], []))
        if [trace._short(n) for n in names] != [n for n, _, _ in ops]:
            raise ValueError(f"{chip}: the trace file's operations do not "
                             f"match ProfileData's")
        ev.scopes[chip] = scopes
    return ev


def scope_of(op_name: str) -> str:
    """The program scope of an operation's metadata name: the path from
    its first ``hmatrix.`` part, without the primitive at its end;
    "" where it has none.
    ``jit(_apply)/hmatrix.apply/permute_out/scatter:`` ->
    ``hmatrix.apply/permute_out``."""
    parts = op_name.rsplit(":", 1)[0].split("/")
    for i, p in enumerate(parts[:-1]):
        if p.startswith(PROGRAM_PREFIX):
            return "/".join(parts[i:-1])
    return ""


# -- the trace file, read as protobuf wire format (tsl's xplane.proto) -------


def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int, or a memoryview of a
    length-delimited field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} in the trace file")
        yield key >> 3, value


def _op_scopes(space) -> dict:
    """chip -> ([metadata name], [scope]) of each operation of its
    ``XLA Ops`` lines, in the order ``ProfileData`` gives them."""
    out = {}
    for num, plane in _fields(space):                  # XSpace.planes
        if num != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for pnum, value in _fields(plane):
            if pnum == 2:                                # XPlane.name
                name = bytes(value).decode()
                if not trace._DEVICE_PLANE.match(name):
                    break
            elif pnum == 3:
                lines.append(value)
            elif pnum in (4, 5):                         # id -> metadata
                key = meta = None
                for enum, v in _fields(value):
                    if enum == 1:
                        key = v
                    elif enum == 2:
                        meta = v
                if pnum == 4:
                    event_meta[key] = meta
                else:
                    stat_names[key] = _field_str(meta, 2)
        else:
            tf_op = {v: k for k, v in stat_names.items()}.get("tf_op")
            known = {}                                   # id -> (name, scope)
            names, scopes = [], []
            for line in lines:
                if _field_str(line, 2) != trace.OPS_LINE:
                    continue
                for lnum, event in _fields(line):
                    if lnum != 4:                        # XLine.events
                        continue
                    mid = next(v for n, v in _fields(event) if n == 1)
                    if mid not in known:
                        meta = event_meta.get(mid)
                        known[mid] = (
                            _field_str(meta, 2) if meta else "",
                            scope_of(_stat_str(meta, tf_op, stat_names)))
                    names.append(known[mid][0])
                    scopes.append(known[mid][1])
            out[name] = (names, scopes)
    return out


def _field_str(msg, number: int) -> str:
    for n, v in _fields(msg):
        if n == number:
            return bytes(v).decode()
    return ""


def _stat_str(meta, stat_id, stat_names) -> str:
    """The string value of stat ``stat_id`` of an event's metadata."""
    if meta is None or stat_id is None:
        return ""
    for n, stat in _fields(meta):
        if n != 5:                                       # XEventMetadata.stats
            continue
        fields = dict(_fields(stat))
        if fields.get(1) != stat_id:
            continue
        if 5 in fields:                                  # str_value
            return bytes(fields[5]).decode()
        if 7 in fields:                                  # ref_value
            return stat_names.get(fields[7], "")
    return ""


# -- reduction ----------------------------------------------------------------


@dataclass
class Reduced(trace.Reduced):
    scope_ns: dict = field(default_factory=dict)    # scope -> ns, chip mean
    op_ns: float = 0.0                  # all operations' ns in the window
    program: dict = field(default_factory=dict)     # name -> [(s, e, args)]
    program_device_ns: dict = field(default_factory=dict)

    def scope_s(self, scope: str) -> float:
        """Device seconds of the operations at or under ``scope``."""
        return sum(t for p, t in self.scope_ns.items()
                   if p == scope or p.startswith(scope + "/")) / 1e9

    def unscoped_pct(self) -> float | None:
        if not self.op_ns:
            return None
        return 100.0 * self.scope_ns.get("", 0.0) / self.op_ns

    def program_s(self, name: str) -> float | None:
        """Mean length of one ``name`` span in seconds; None if none."""
        spans = self.program.get(name, ())
        return sum(e - s for s, e, _ in spans) / len(spans) / 1e9 \
            if spans else None

    def program_args(self, name: str) -> list:
        """The arguments of each ``name`` span."""
        return [a for _, _, a in self.program.get(name, ())]

    def family(self, prefix: str) -> list:
        """The scopes one level deep that start with ``prefix``, in level
        order: ``hmatrix.apply/lowrank.`` -> [``hmatrix.apply/lowrank.L3``,
        ...]."""
        depth = prefix.count("/") + 1
        found = {"/".join(p.split("/")[:depth]) for p in self.scope_ns
                 if p.startswith(prefix)}
        return sorted(found, key=lambda p: int(re.sub(r"\D", "", p.rsplit(
            ".", 1)[-1]) or 0))

    def parts_s(self, scopes: list, parts) -> dict:
        """Device seconds of each part, summed over ``scopes``:
        ``{part: Σ scope_s(f"{scope}/{part}")}``."""
        return {p: sum(self.scope_s(f"{s}/{p}") for s in scopes)
                for p in parts}


def reduce(ev: Events, top: int = 10) -> Reduced:
    base = _trace_reduce(ev, top)
    w0, w1 = base.window
    chips = sorted(ev.device)
    busy = {c: trace.merge((s, e) for _, s, e in ev.device[c])
            for c in chips}

    per_op: dict = defaultdict(float)
    scope_ns: dict = defaultdict(float)
    for c in chips:
        scopes = ev.scopes.get(c) or [""] * len(ev.device[c])
        for (name, s, e), scope in zip(ev.device[c], scopes):
            if trace._CONTAINER.match(name):
                continue
            t = max(0, min(e, w1) - max(s, w0))
            per_op[f"{name} [{scope}]" if scope else name] += t
            scope_ns[scope] += t
    n = len(chips)
    top_ops = sorted(((k, t / n / 1e9) for k, t in per_op.items() if t > 0),
                     key=lambda x: -x[1])[:top]

    gaps = []
    for c in chips:
        edges = [w0] + [x for iv in busy[c] for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                gaps.append((s, e))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]

    program: dict = defaultdict(list)
    for _, name, s, e, args in ev.program:
        if w0 <= s < w1:
            program[name].append((s, e, args))
    program_dev = {name: sum(trace.covered(busy[c], s, e) for c in chips
                             for s, e, _ in iv) / n
                   for name, iv in program.items()}

    fields = dict(vars(base), top_ops=top_ops,
                  idle_gaps=[(_label(ev.host, (s + e) / 2), (e - s) / 1e9)
                             for s, e in gaps])
    return Reduced(**fields, scope_ns={k: t / n for k, t in scope_ns.items()},
                   op_ns=sum(scope_ns.values()) / n, program=dict(program),
                   program_device_ns=program_dev)


def _label(host, t) -> str:
    """Innermost benchmark span, program span and other host event at t."""
    best = {}
    for _, name, s, e in host:
        if not s <= t <= e or name == trace.WINDOW:
            continue
        kind = ("bench" if name.startswith(trace.BENCH_PREFIX) else
                "program" if name.startswith(PROGRAM_PREFIX) else "host")
        if kind not in best or e - s < best[kind][1]:
            best[kind] = (name, e - s)
    parts = [best[k][0] for k in ("bench", "program", "host") if k in best]
    return " / ".join(parts) if parts else "no host span"


# -- the per-layer numbers the program's names give -------------------------

# read by bench/metrics/<name>.py, each from a reduction of this file
METRICS = ("apply_lowrank_ms", "apply_dense_ms", "apply_permute_ms",
           "build_aca_dev_s", "build_fetch_s", "serve_queue_ms",
           "serve_pad_pct", "serve_fetch_ms")


def scoped(run) -> Reduced | None:
    """The reduction a per-layer reader sees, if it is this file's; None
    where it is :func:`trace.reduce`'s, which keeps no program names."""
    return run.trace if hasattr(run.trace, "scope_ns") else None


def report(r: Reduced, top: int = 16) -> dict:
    """The numbers of :data:`METRICS` that the trace gives, the unscoped
    share, the scopes that took most device time (s, chip mean) and the
    program spans (count, mean s, device s inside)."""
    from bench import suite
    out = {}
    for name in METRICS:
        got = suite.reader(name)(SimpleNamespace(trace=r))
        if got is not None:
            value, extra = got if isinstance(got, tuple) else (got, {})
            out[name] = {"value": value, **extra}
    scopes = sorted(((p, t / 1e9) for p, t in r.scope_ns.items() if p),
                    key=lambda x: -x[1])[:top]
    spans = {name: [len(iv), r.program_s(name),
                    r.program_device_ns[name] / 1e9]
             for name, iv in sorted(r.program.items())}
    return {"metrics": out, "unscoped_pct": r.unscoped_pct(),
            "scopes_s": scopes, "program_spans": spans}


def main(argv=None, **run_kw) -> int:
    """``bench/run.py``'s ``main`` (``run_kw`` passed on) with this
    reduction in place of :func:`trace.reduce`; prints
    ``{"scoped": report}`` after its result line."""
    from bench import run
    got = {}

    def scoped_reduce(ev, top=10):
        got["reduced"] = reduce(ev, top)
        return got["reduced"]

    saved = trace.load, trace.reduce
    trace.load, trace.reduce = load, scoped_reduce
    try:
        rc = run.main(argv, **run_kw)
    finally:
        trace.load, trace.reduce = saved
    if "reduced" in got:
        print(json.dumps({"scoped": report(got["reduced"])}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
