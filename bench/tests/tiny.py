"""A checkout root whose BENCHMARK.json adds a tiny copy of every cell:
new configuration and limits files and new entries, no code edited."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# 2048 points in leaves of 64
TINY = {"n_points": 2048, "c_leaf": 64}


def make_root(tmp_path, size: dict = TINY) -> tuple[str, dict]:
    """Copy of the benchmark under ``tmp_path`` with a ``tiny_<cell>`` for
    every cell at ``size``; returns the root and {cell: tiny cell}."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {}
    for cfg in list(bench["configs"]):
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        body.update(size, name="tiny_" + cfg["name"])
        path = f"bench/configs/tiny_{cfg['name']}.json"
        (root / path).write_text(json.dumps(body))
        bench["configs"].append(dict(cfg, name=body["name"], file=path))
    for w in list(bench["workloads"]):
        tiny = "tiny_" + w["name"]
        names[w["name"]] = tiny
        bench["workloads"].append(dict(w, name=tiny,
                                       config="tiny_" + w["config"]))
        shutil.copy(root / "bench" / "limits" / (w["name"] + ".json"),
                    root / "bench" / "limits" / (tiny + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [names[c] for c in
                                               m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), names
