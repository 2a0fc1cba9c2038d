"""Finds what ``BENCHMARK.json`` names, each a file of its own:

    bench/configs/<config>.json     the deployment, as ``configs[].file``
    bench/traffic/<traffic>.json    the loop and its parameters
    bench/limits/<cell>.json        each compared number's limit, with the
                                    readings it was set from
    bench/loops/<loop>.py           the traffic's loop (``Loop``, ``control``)
    bench/arrivals/<name>.py        ``gaps(traffic, seconds, seed)`` of an
                                    open-loop arrival process
    bench/points/<name>.py          ``points(config)``: the design
    bench/kernels/<name>.py         ``kernel(y, y')`` of the plain reference
    bench/metrics/<metric>.py       ``read(run)`` for one per-layer metric

A later cell, configuration, arrival process or metric is a new file and a
new entry; no file here is edited for it.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    root: str                       # checkout that holds BENCHMARK.json
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list                # metric entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = os.path.join(root, "bench")
    return Cell(
        name=name, root=root, chips=int(w["chips"]),
        config=_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_json(os.path.join(bench_dir, "traffic",
                                   w["traffic"] + ".json")),
        limits=_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


@functools.cache
def module(kind: str, name: str, root: str = ROOT):
    """``<root>/bench/<kind>/<name>.py``, loaded once (a name may hold
    dots, so it is loaded from its path, not imported)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop(name: str, root: str = ROOT):
    return module("loops", name, root)


def arrivals(name: str, root: str = ROOT):
    return module("arrivals", name, root).gaps


def points(name: str, root: str = ROOT):
    return module("points", name, root).points


def kernel(name: str, root: str = ROOT):
    return module("kernels", name, root).kernel


def reader(metric: str, root: str = ROOT):
    return module("metrics", metric, root).read
