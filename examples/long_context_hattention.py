"""The paper's technique inside the LM: H-matrix attention vs full attention.

Compares output agreement and score-FLOP counts of `h_attention` against
exact attention on a long sequence with a smooth attention landscape, then
runs a forward pass of the qwen2.5-14b-hmatrix smoke config.

    PYTHONPATH=src python examples/long_context_hattention.py
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hattention import causal_hmatrix_plan, h_attention
from repro.configs.registry import get_smoke
from repro.models.api import get_model
from repro.runtime.compile_cache import enable_compile_cache


# module-level jit: a jax.jit(lambda ...) inside main() would recompile on
# every call of main (fresh cache key per lambda object)
@functools.partial(jax.jit, static_argnames=("c_leaf", "rank"))
def _h_fn(q, k, v, c_leaf, rank):
    return h_attention(q, k, v, c_leaf=c_leaf, rank=rank)


def main():
    enable_compile_cache()
    s, c_leaf, rank = 4096, 256, 16
    plan = causal_hmatrix_plan(s, c_leaf)
    n_adm = sum(len(r) for r, _ in plan["levels"].values())
    dense_cells = plan["n_leaf"] * (2 * c_leaf * c_leaf) - c_leaf * c_leaf
    adm_cells = sum(len(r) * (s >> l) ** 2 for l, (r, _) in plan["levels"].items())
    print(f"S={s}, c_leaf={c_leaf}: {n_adm} admissible blocks, "
          f"{plan['n_leaf'] * 2 - 1} dense leaf blocks")
    print(f"score-entry budget: dense {dense_cells:,} + rank-{rank} ACA on "
          f"{adm_cells:,} far-field cells (vs {s * s:,} full)")

    # smooth q/k -> far field genuinely low-rank
    rng = np.random.RandomState(0)
    t = np.linspace(0, 6 * np.pi, s)
    d = 32
    feats = np.stack([np.sin(t * (i + 1) / d) for i in range(d)], -1) * 2.0
    q = jnp.asarray((feats[None, :, None, :] + 0.01 * rng.randn(1, s, 2, d)),
                    jnp.float32)
    k = jnp.asarray((feats[None, :, None, :] + 0.01 * rng.randn(1, s, 1, d)),
                    jnp.float32)
    v = jnp.asarray(rng.randn(1, s, 1, d), np.float32)

    out_h = _h_fn(q, k, v, c_leaf, rank).block_until_ready()
    t0 = time.perf_counter()
    out_h = _h_fn(q, k, v, c_leaf, rank).block_until_ready()
    print(f"h_attention: {time.perf_counter() - t0:.3f}s")

    # exact reference
    def full(q, k, v):
        qf = q.astype(jnp.float32).reshape(1, s, 1, 2, d) / jnp.sqrt(d)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k)
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None, None], sc, -1e30)
        p = jax.nn.softmax(sc, -1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v)
        return o.transpose(0, 3, 1, 2, 4).reshape(1, s, 2, d)

    full_fn = jax.jit(full)
    out_f = full_fn(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    out_f = full_fn(q, k, v).block_until_ready()
    print(f"full attention: {time.perf_counter() - t0:.3f}s")
    rel = float(jax.device_get(
        jnp.linalg.norm(out_h - out_f) / jnp.linalg.norm(out_f)))
    print(f"relative agreement: {rel:.3e}")

    # whole-model forward with the hmatrix backend
    cfg = get_smoke("qwen2.5-14b-hmatrix").replace(dtype="float32", h_c_leaf=128)
    model = get_model(cfg)
    params = model["init_params"](jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 1024), 0, cfg.vocab_size)
    logits, _ = model["forward"](params=params, tokens=tokens, mode="train")
    finite = bool(jax.device_get(jnp.all(jnp.isfinite(logits))))
    print(f"qwen2.5-14b-hmatrix smoke forward at S=1024: logits {logits.shape}, "
          f"finite={finite}")


if __name__ == "__main__":
    main()
