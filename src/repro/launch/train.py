"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --smoke --ckpt-dir /tmp/ckpt

Wires together: config registry, deterministic data pipeline, train_step
(remat + microbatch accumulation + ZeRO AdamW), checkpoint manager (atomic,
async, keep-k), preemption handler, straggler monitor, and restart
supervisor.  ``--smoke`` uses the reduced config (CPU-runnable); the full
config path is exercised by the dry-run.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_arch, get_smoke, list_archs
from repro.data.pipeline import DataConfig, make_batch
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import PreemptionHandler
from repro.serve.faults import StragglerMonitor, run_with_restarts
from repro.train.optimizer import AdamWConfig
from repro.train.step import make_train_step


def train_loop(cfg, args):
    init_state, train_step = make_train_step(
        cfg,
        AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                    total_steps=args.steps,
                    compression="bf16_ef" if args.compress_grads else "none"),
        microbatches=args.microbatches)
    step_fn = jax.jit(train_step, donate_argnums=(0,))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.batch, seed=args.seed)
    mgr = CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
    preempt = PreemptionHandler().install()
    straggler = StragglerMonitor()

    state = init_state(jax.random.PRNGKey(args.seed))
    start = 0
    if mgr.latest_step() is not None:
        state, manifest = mgr.restore(state)
        start = manifest["extra"]["data_step"]
        print(f"[restore] resumed from step {start}")

    with_embeds = cfg.frontend == "audio_stub"
    t_last = time.perf_counter()
    for step in range(start, args.steps):
        batch = make_batch(dcfg, step, d_model=cfg.d_model,
                           with_embeds=with_embeds)
        state, metrics = step_fn(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            # ONE device fetch for every logged scalar: three float() calls
            # would block the dispatch pipeline three times per log step
            m = jax.device_get(metrics)
            loss = float(m["loss"])
            dt = time.perf_counter() - t_last
            t_last = time.perf_counter()
            slow = straggler.record("host0", dt)
            print(f"step {step:6d}  loss {loss:.4f}  lr {float(m['lr']):.2e}"
                  f"  gnorm {float(m['grad_norm']):.2f}  {dt:.2f}s"
                  f"{'  [STRAGGLER]' if slow else ''}", flush=True)
        if step > 0 and step % args.ckpt_every == 0:
            mgr.save(step + 1, state, extra={"data_step": step + 1})
        if preempt.preempted:
            print("[preempt] SIGTERM received -> final checkpoint")
            mgr.wait()
            mgr.save(step + 1, state, extra={"data_step": step + 1})
            mgr.wait()
            return state
    mgr.wait()
    mgr.save(args.steps, state, extra={"data_step": args.steps})
    mgr.wait()
    preempt.uninstall()
    return state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke(args.arch) if args.smoke else get_arch(args.arch))
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    run_with_restarts(lambda: train_loop(cfg, args),
                      max_restarts=args.max_restarts,
                      on_restart=lambda n, e: print(f"[restart {n}] after: {e}"))


if __name__ == "__main__":
    main()
