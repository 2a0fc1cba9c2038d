"""Serving launcher: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
        --prompt-len 32 --decode-steps 16 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch, get_smoke, list_archs
from repro.models.api import get_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve.step import greedy_sample, make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke(args.arch) if args.smoke else get_arch(args.arch))
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    model = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model["init_params"](key)

    b, s = args.batch, args.prompt_len
    max_seq = s + args.decode_steps
    prompts = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))

    t0 = time.perf_counter()
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
        logits, caches = prefill(params, prompts, frames)
    else:
        logits, caches = prefill(params, prompts)
    # grow caches to decode capacity
    def grow(x):
        if hasattr(x, "ndim") and x.ndim >= 3:
            for axis in range(x.ndim):
                if x.shape[axis] == s and x.ndim - axis == 3:
                    pad = [(0, 0)] * x.ndim
                    pad[axis] = (0, args.decode_steps)
                    return jnp.pad(x, pad)
        return x
    caches = jax.tree.map(grow, caches)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {b}x{s} tokens in {t_prefill:.3f}s "
          f"({b * s / t_prefill:.0f} tok/s)")

    tok = greedy_sample(logits[:, -1:], cfg.vocab_size)
    generated = [tok]
    t0 = time.perf_counter()
    for i in range(args.decode_steps - 1):
        logits, caches = decode(params, tok, caches,
                                jnp.asarray(s + i, jnp.int32))
        tok = greedy_sample(logits, cfg.vocab_size)
        generated.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    out = jnp.concatenate(generated, axis=1)
    print(f"decode: {args.decode_steps - 1} steps in {t_dec:.3f}s "
          f"({b * (args.decode_steps - 1) / max(t_dec, 1e-9):.0f} tok/s)")
    print("generated token ids (first row):", jax.device_get(out[0]).tolist())


if __name__ == "__main__":
    main()
