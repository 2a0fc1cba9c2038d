"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain event lists (:func:`load`): the device
operations of each chip, and the host spans that the benchmark itself
opens with ``jax.profiler.TraceAnnotation`` (names starting ``bench.``)
together with the other host events of the main thread.  Everything else
here works on those lists, so that a small recorded trace can test it:

* busy time: the union of the intervals in which an operation ran on a
  chip, averaged over the chips;
* device time inside a benchmark span: the busy union clipped to each
  span (a closed loop waits on every call inside its span, so this is the
  device time of the call, whatever the program names its operations);
* the idle gaps of the window, each labelled by the innermost benchmark
  span and host event that cover it;
* the device operations that took most time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

BENCH_PREFIX = "bench."
WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# operations that contain others (a loop, a branch, a call) count towards
# busy time through their body, and are left out of the top operations
_CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")
# the line of a TPU plane whose events are the executed operations
OPS_LINE = "XLA Ops"


@dataclass
class Events:
    """Plain events in nanoseconds on the trace's one clock.

    ``device``: chip -> [(name, start, end)] of executed operations;
    ``host``: [(thread, name, start, end)] of host events."""
    device: dict = field(default_factory=dict)
    host: list = field(default_factory=list)

    @classmethod
    def from_json(cls, obj: dict) -> "Events":
        return cls(device={k: [tuple(e) for e in v]
                           for k, v in obj["device"].items()},
                   host=[tuple(e) for e in obj["host"]])


def load(trace_dir: str) -> Events:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ev = Events()
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(_short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            ev.device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        ev.host.append((line.name, e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return ev


def _short(op: str) -> str:
    """An operation's HLO text cut to its name and result type:
    ``%fusion.34 = f32[22746,256,64]``."""
    return op.split("{", 1)[0].strip()


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, t0, t1) -> float:
    """Length of the part of [t0, t1] that the merged (sorted, disjoint)
    intervals cover."""
    i = max(0, bisect.bisect_right(merged, (t0,)) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= t1:
            break
        total += max(0, min(e, t1) - max(s, t0))
    return total


@dataclass
class Reduced:
    window: tuple                   # (start, end) of bench.window, ns
    chips: int
    busy_ns: float                  # busy union in the window, chip mean
    spans: dict                     # bench span name -> [(start, end)]
    span_device_ns: dict            # bench span name -> device ns inside
    top_ops: list                   # [(name, seconds)], chip mean
    idle_gaps: list                 # [(label, seconds)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_ns / (self.window[1] - self.window[0]))

    def device_s_per_span(self, name: str):
        """Mean device seconds inside one ``name`` span; None if none."""
        n = len(self.spans.get(name, ()))
        if not n:
            return None
        return self.span_device_ns[name] / n / 1e9


def reduce(ev: Events, top: int = 10) -> Reduced:
    windows = [(s, e) for _, name, s, e in ev.host if name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    chips = sorted(ev.device)
    if not chips or not any(ev.device[c] for c in chips):
        raise ValueError("trace has no device operations")
    busy = {c: merge((s, e) for _, s, e in ev.device[c]) for c in chips}

    spans: dict = defaultdict(list)
    for _, name, s, e in ev.host:
        if name.startswith(BENCH_PREFIX) and name != WINDOW:
            spans[name].append((s, e))
    span_dev = {name: sum(covered(busy[c], s, e) for c in chips
                          for s, e in iv) / len(chips)
                for name, iv in spans.items()}

    per_op: dict = defaultdict(float)
    for c in chips:
        for name, s, e in ev.device[c]:
            if not _CONTAINER.match(name):
                per_op[name] += max(0, min(e, w1) - max(s, w0))
    top_ops = sorted(((n, t / len(chips) / 1e9) for n, t in per_op.items()
                      if t > 0), key=lambda x: -x[1])[:top]

    gaps = []
    for c in chips:
        edges = [w0] + [x for iv in busy[c] for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                gaps.append((s, e))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]

    return Reduced(window=(w0, w1), chips=len(chips),
                   busy_ns=sum(covered(busy[c], w0, w1) for c in chips)
                   / len(chips),
                   spans=dict(spans), span_device_ns=span_dev,
                   top_ops=top_ops,
                   idle_gaps=[(_label(ev.host, (s + e) / 2), (e - s) / 1e9)
                              for s, e in gaps])


def _label(host, t) -> str:
    """Innermost benchmark span, and innermost other host event, at t."""
    best_bench = best_host = None
    for thread, name, s, e in host:
        if s <= t <= e and name != WINDOW:
            if name.startswith(BENCH_PREFIX):
                if best_bench is None or e - s < best_bench[1]:
                    best_bench = (name, e - s)
            elif best_host is None or e - s < best_host[1]:
                best_host = (name, e - s)
    parts = [p[0] for p in (best_bench, best_host) if p is not None]
    return " / ".join(parts) if parts else "no host span"

