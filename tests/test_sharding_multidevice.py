"""Multi-device sharding tests — run in a subprocess so the forced host
device count never leaks into the other tests (assignment: smoke tests and
benches must see 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_debug_mesh
    from repro.parallel.mesh_ctx import use_mesh, resolve_spec, axis_size
    from repro.parallel.sharding import param_specs, opt_state_specs, zero1_spec
    from repro.configs.registry import get_smoke
    from repro.models.api import get_model

    mesh = make_debug_mesh(2, 4)
    with use_mesh(mesh):
        assert axis_size("model") == 4 and axis_size("data") == 2
        # resolve drops non-divisible / missing axes
        assert resolve_spec((9, 8), P("model", None)) == P(None, None)
        assert resolve_spec((8, 9), P("data", "model")) == P("data", None)
        assert resolve_spec((16,), P(("pod", "data"))) == P("data")

        cfg = get_smoke("qwen2.5-14b").replace(dtype="float32")
        model = get_model(cfg)
        struct = jax.eval_shape(model["init_params"], jax.random.PRNGKey(0))
        specs = param_specs(struct, cfg.num_experts)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        by_path = {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path): s for path, s in flat}
        wq = [s for k, s in by_path.items() if k.endswith("attn/wq")]
        assert wq and all(s[-1] == "model" for s in wq), wq
        wo = [s for k, s in by_path.items() if k.endswith("attn/wo")]
        assert wo and all(s[-2] == "model" for s in wo), wo

        # ZeRO-1 adds 'data' on a free divisible dim
        z = zero1_spec(P(None, "model"), (64, 128))
        assert "data" in jax.tree_util.tree_leaves([z]) or z == P("data", "model")

        # end-to-end: tiny train step on the debug mesh with real arrays
        from repro.train.step import make_train_step
        from repro.train.optimizer import AdamWConfig
        init_state, train_step = make_train_step(
            cfg, AdamWConfig(warmup_steps=1, total_steps=10), microbatches=2)
        state = init_state(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                    cfg.vocab_size)
        state, metrics = jax.jit(train_step)(state, {"tokens": tokens,
                                                     "labels": tokens})
        assert bool(jnp.isfinite(metrics["loss"])), metrics
        # decode on mesh: MoE arch covers EP-eligible path too
        cfg2 = get_smoke("granite-moe-1b-a400m").replace(dtype="float32")
        model2 = get_model(cfg2)
        params2 = model2["init_params"](jax.random.PRNGKey(0))
        caches = model2["init_caches"](4, 32)
        logits, _ = model2["forward"](params=params2,
                                      tokens=jnp.zeros((4, 1), jnp.int32),
                                      mode="decode", caches=caches,
                                      cache_len=jnp.asarray(3, jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))
    print("MULTIDEVICE_OK")
""")


def test_sharding_rules_and_debug_mesh_train():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"    # forced devices are host devices
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "MULTIDEVICE_OK" in out.stdout, out.stdout + "\n" + out.stderr
