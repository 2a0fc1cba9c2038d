import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), which is why the docstring sits below them and
# no `from __future__` import is used in this module.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the right step function (train_step / prefill_step
/ decode_step) against ShapeDtypeStruct inputs on the production mesh,
compiles it (SPMD partitioning for 256 or 512 chips), prints
``memory_analysis()`` (proves it fits) and ``cost_analysis()`` (FLOPs/bytes),
runs the trip-count-aware HLO analyzer, and writes a JSON artifact under
results/dryrun/ for the roofline table.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.flops import attention_extra_flops, model_flops
from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import V5E, chip_peaks, roofline_terms
from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import get_arch, get_shape, iter_cells, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (decode_input_specs, prefill_input_specs,
                                state_struct_and_specs, train_input_specs)
from repro.models.api import count_params_analytic, get_model
from repro.parallel.mesh_ctx import use_mesh
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.optimizer import AdamWConfig
from repro.train.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# Microbatch counts chosen so per-device activation residency fits 16 GB HBM
# (remat keeps one microbatch x one layer live; see DESIGN.md §5).
MICROBATCHES = {
    "whisper-tiny": 1, "smollm-135m": 2, "granite-moe-1b-a400m": 2,
    "gemma-7b": 8, "phi3-medium-14b": 8, "qwen2.5-14b": 8,
    "qwen2.5-14b-hmatrix": 8, "mixtral-8x7b": 32, "chameleon-34b": 16,
    "xlstm-1.3b": 4, "zamba2-7b": 8,
}


def _named(mesh, spec_tree, struct_tree=None):
    from repro.parallel.mesh_ctx import resolve_spec, use_mesh as _um

    def mk(s, x=None):
        if x is not None:
            s = resolve_spec(x.shape, s)
        else:
            s = P(*[_drop_missing(e, mesh) for e in s])
        return NamedSharding(mesh, s)

    if struct_tree is not None:
        return jax.tree.map(lambda s, x: mk(s, x), spec_tree, struct_tree,
                            is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(mk, spec_tree, is_leaf=lambda x: isinstance(x, P))


def _drop_missing(entry, mesh):
    if entry is None:
        return None
    names = entry if isinstance(entry, tuple) else (entry,)
    kept = [n for n in names if n in mesh.axis_names]
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Lower + compile one cell; returns (compiled, lowered, meta)."""
    cfg = get_arch(arch_name)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    runs, reason = shape_applicable(cfg, shape)
    if not runs:
        return None, None, {"skipped": True, "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    with use_mesh(mesh):
        if shape.kind == "train":
            mb = MICROBATCHES.get(arch_name, 4)
            init_state, train_step = make_train_step(
                cfg, AdamWConfig(), microbatches=mb, remat=True)
            state_struct, state_specs = state_struct_and_specs(cfg, init_state)
            batch_struct, batch_specs = train_input_specs(cfg, shape)
            state_sh = _named(mesh, state_specs, state_struct)
            step = jax.jit(train_step,
                           in_shardings=(state_sh,
                                         _named(mesh, batch_specs, batch_struct)),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))
            lowered = step.lower(state_struct, batch_struct)
        elif shape.kind == "prefill":
            prefill = make_prefill_step(cfg)
            inputs, in_specs = prefill_input_specs(cfg, shape)
            state_struct, state_specs = _param_struct(cfg)
            args = [state_struct, inputs["tokens"]]
            shardings = [_named(mesh, state_specs, state_struct),
                         _named(mesh, in_specs["tokens"], inputs["tokens"])]
            if "embeds" in inputs:
                args.append(inputs["embeds"])
                shardings.append(_named(mesh, in_specs["embeds"], inputs["embeds"]))
            step = jax.jit(prefill, in_shardings=tuple(shardings))
            lowered = step.lower(*args)
        else:  # decode
            decode = make_decode_step(cfg)
            model = get_model(cfg)
            inputs, in_specs = decode_input_specs(cfg, shape, model)
            state_struct, state_specs = _param_struct(cfg)
            step = jax.jit(
                decode,
                in_shardings=(_named(mesh, state_specs, state_struct),
                              _named(mesh, in_specs["tokens"], inputs["tokens"]),
                              _named(mesh, in_specs["caches"], inputs["caches"]),
                              _named(mesh, in_specs["cache_len"],
                                     inputs["cache_len"])),
                donate_argnums=(2,))
            lowered = step.lower(state_struct, inputs["tokens"],
                                 inputs["caches"], inputs["cache_len"])
        t0 = time.time()
        compiled = lowered.compile()
        meta = {"skipped": False, "compile_s": time.time() - t0,
                "mesh": "multi" if multi_pod else "single",
                "chips": 512 if multi_pod else 256}
    return compiled, lowered, meta


def _param_struct(cfg):
    from repro.parallel.sharding import param_specs
    model = get_model(cfg)
    struct = jax.eval_shape(model["init_params"], jax.random.PRNGKey(0))
    return struct, param_specs(struct, cfg.num_experts)


def analyze_cell(arch_name: str, shape_name: str, multi_pod: bool,
                 overrides: dict | None = None, tag: str = "") -> dict:
    cfg = get_arch(arch_name)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    record = {"arch": arch_name, "shape": shape_name,
              "mesh": "multi" if multi_pod else "single", "tag": tag}
    try:
        compiled, lowered, meta = lower_cell(arch_name, shape_name, multi_pod,
                                             overrides)
    except Exception as e:
        record.update(error="".join(traceback.format_exception_only(e)).strip())
        traceback.print_exc()
        return record
    record.update(meta)
    if meta.get("skipped"):
        return record

    chips = meta["chips"]
    ma = compiled.memory_analysis()
    record["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes_est": ma.argument_size_in_bytes + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
    }
    ca = compiled.cost_analysis()
    record["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                          "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    stats = analyze_hlo(compiled.as_text())
    record["hlo"] = {
        "dot_flops": stats.dot_flops,
        "traffic_bytes": stats.traffic_bytes,
        "collective_bytes": stats.collective_bytes,
        "loops": stats.loops,
        "n_collectives": len(stats.collectives),
        "collectives_by_op": _group_collectives(stats.collectives),
    }
    mf = model_flops(cfg, shape) + attention_extra_flops(cfg, shape)
    terms = roofline_terms(
        flops_per_chip=stats.dot_flops,
        hbm_bytes_per_chip=stats.traffic_bytes,
        collective_bytes_per_chip=stats.collective_bytes,
        model_flops_per_chip=mf / chips, device_kind=V5E)
    record["model_flops_global"] = mf
    record["params"] = count_params_analytic(cfg)
    record["roofline"] = terms.as_dict()

    # --- ideal-bytes memory roofline (binds decode/prefill fractions) -----
    tp = 16
    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    param_bytes = record["params"]["total"] * dtype_bytes
    cache_bytes = 0
    if shape.kind == "decode":
        inputs, _ = decode_input_specs(cfg, shape)
        cache_bytes = sum(x.size * jnp.dtype(x.dtype).itemsize
                          for x in jax.tree.leaves(inputs["caches"]))
    if shape.kind == "train":
        mb = MICROBATCHES.get(arch_name, 4)
        ideal_bytes = 3 * param_bytes / tp + 12 * record["params"]["total"] / chips
    elif shape.kind == "prefill":
        ideal_bytes = param_bytes / tp
    else:
        ideal_bytes = param_bytes / tp + cache_bytes / chips
    peaks = chip_peaks(V5E)
    ideal_mem_s = ideal_bytes / peaks.hbm_bw
    ideal_s = max(ideal_mem_s, mf / chips / peaks.flops)
    record["ideal"] = {"bytes_per_chip": ideal_bytes,
                       "memory_s": ideal_mem_s,
                       "bound_s": ideal_s,
                       "cache_bytes_global": cache_bytes}
    # roofline fraction: ideal bound (compute OR minimum-bytes memory,
    # whichever binds) over the modelled step time
    record["roofline"]["roofline_fraction"] = (
        ideal_s / terms.step_time_s if terms.step_time_s > 0 else 0.0)
    return record


def _group_collectives(colls):
    by = {}
    for c in colls:
        e = by.setdefault(c["op"], {"count": 0, "bytes": 0.0})
        e["count"] += 1
        e["bytes"] += c["bytes"] * c["mult"]
    return by


def save_record(record: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = f"-{record['tag']}" if record.get("tag") else ""
    fn = f"{record['arch']}--{record['shape']}--{record['mesh']}{tag}.json"
    path = os.path.join(RESULTS_DIR, fn)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=float)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list_archs() + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch, shape, runs, reason in iter_cells():
            cells.append((arch.name, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    for arch_name, shape_name in cells:
        for multi in meshes:
            t0 = time.time()
            rec = analyze_cell(arch_name, shape_name, multi, tag=args.tag)
            path = save_record(rec)
            status = ("SKIP: " + rec.get("reason", "")) if rec.get("skipped") \
                else ("ERROR: " + rec["error"][:120]) if "error" in rec \
                else (f"ok compile={rec['compile_s']:.1f}s "
                      f"dom={rec['roofline']['dominant']} "
                      f"frac={rec['roofline']['roofline_fraction']:.3f}")
            print(f"[{time.time()-t0:7.1f}s] {arch_name:24s} {shape_name:12s} "
                  f"{'multi' if multi else 'single':6s} {status}", flush=True)


if __name__ == "__main__":
    main()
