"""GPipe-style pipeline parallelism over a mesh axis (optional layout).

The default multi-pod layout is hierarchical DP over the "pod" axis
(DESIGN.md §5); this module provides the alternative: treat an axis as
pipeline stages, microbatches streamed with collective_permute handoffs
inside a shard_map.  Kept deliberately minimal — it demonstrates the
schedule and the collective pattern; bubble-optimised schedules (1F1B,
interleaved) are enumerated in DESIGN.md as future work.

fn signature: stage_fn(stage_params, x) -> x; params are stacked over the
leading stage axis and sharded over ``axis``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(stage_params, x_microbatches, *, axis: str, n_stages: int,
                   stage_fn):
    """Run microbatches through pipeline stages living on mesh axis ``axis``.

    stage_params: pytree with leaves stacked on a leading (n_stages,) dim,
        sharded so each device along ``axis`` holds its stage's slice.
    x_microbatches: (n_micro, mb, ...) inputs.
    Returns (n_micro, mb, ...) outputs (as produced by the LAST stage).

    Implemented as a shard_map over ``axis``: each step every stage runs
    its resident microbatch, then activations shift one stage forward with
    ``ppermute`` (the canonical GPipe loop: n_micro + n_stages - 1 ticks).
    """
    n_micro = x_microbatches.shape[0]

    def per_stage(params_local, xs_local):
        # params_local: (1, ...) this stage's params; xs_local: full stream
        # (shard_map with replicated xs: every stage sees the stream, only
        # stage 0 injects it).
        stage_id = lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params_local)
        total = n_micro + n_stages - 1
        # mark the carries as device-varying along the pipeline axis
        buf = lax.pcast(jnp.zeros_like(xs_local[0]), (axis,), to="varying")
        outs = lax.pcast(jnp.zeros((n_micro,) + xs_local.shape[1:],
                                   xs_local.dtype), (axis,), to="varying")

        def tick(carry, t):
            buf, outs = carry
            # stage 0 loads microbatch t (if in range); others use shifted
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage_id == 0,
                             xs_local[inject], buf)
            y = stage_fn(params, x_in)
            # last stage stores its result for microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            store = (stage_id == n_stages - 1) & (t >= n_stages - 1)
            updated = lax.dynamic_update_index_in_dim(outs, y, out_idx, 0)
            outs = jnp.where(store, updated, outs)
            # shift activations one stage forward
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(total))
        # only the last stage holds results (zeros elsewhere): one psum
        # replicates them for the P() out_spec
        return lax.psum(outs, axis)

    mesh = jax.sharding.Mesh(
        *_current_mesh_parts(axis))
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )(stage_params, x_microbatches)


def _current_mesh_parts(axis: str):
    from repro.parallel.mesh_ctx import current_mesh
    mesh = current_mesh()
    if mesh is None:
        raise RuntimeError("pipeline_apply requires an active mesh")
    return mesh.devices, mesh.axis_names
