"""GPipe-style microbatch pipelining over the ``pod`` mesh axis.

When the ``pod`` axis runs in ``pipeline`` role (MeshConfig.pod_role),
the layer stack is split into one stage per pod and microbatches flow
through the stages; in steady state every pod computes while activations
for the next microbatch are in flight (the classic 1F schedule — the
bubble is (S-1)/(M+S-1) of the schedule).

Implemented as a shard_map over the mesh: each pod holds its stage's
weights (``w`` sharded over ``pod`` on dim 0); per schedule tick every
stage runs its microbatch and hands the result to the next stage with a
ring ``ppermute``.  Stage outputs from the last stage are reassembled
and replicated with a final psum.
"""
from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(mesh: Mesh, stage_fn: Callable[..., jax.Array],
                      x: jax.Array, w: Any, microbatches: int) -> jax.Array:
    """Run ``stage_fn(stage_idx, w_stage, x_mb)`` as a pipeline.

    x: [B, ...] replicated input, split into ``microbatches`` along dim 0;
    w: [n_stages, ...] per-stage weights, sharded over ``pod``.
    Returns the pipelined output, numerically equal to applying all
    stages in sequence to every microbatch.
    """
    assert "pod" in mesh.axis_names, mesh.axis_names
    n_stages = dict(mesh.shape)["pod"]
    m = microbatches
    assert x.shape[0] % m == 0, (x.shape, m)
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(x_full: jax.Array, w_local: Any) -> jax.Array:
        stage = jax.lax.axis_index("pod")
        mbs = x_full.reshape((m, x_full.shape[0] // m) + x_full.shape[1:])
        mbs = mbs.astype(jnp.float32)
        mb_shape = mbs.shape[1:]

        def tick(t, carry):
            out, recv = carry
            # stage 0 injects microbatch t; later stages consume the
            # activation handed over by the previous stage last tick
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.minimum(t, m - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, feed, recv)
            y = stage_fn(stage, w_local, inp).astype(jnp.float32)
            # a stage is idle while the pipeline fills/drains
            active = (t - stage >= 0) & (t - stage < m)
            y = jnp.where(active, y, 0.0)
            # the last stage lands microbatch t-(S-1) in the output
            oi = jnp.clip(t - (n_stages - 1), 0, m - 1)
            write = (stage == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out, oi, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(write, y, cur), oi, 0)
            recv = jax.lax.ppermute(y, "pod", ring)
            return out, recv

        out, _ = jax.lax.fori_loop(
            0, m + n_stages - 1, tick,
            (jnp.zeros_like(mbs), jnp.zeros(mb_shape, jnp.float32)))
        # only the last stage wrote real outputs; psum replicates them
        out = jax.lax.psum(out, "pod")
        return out.reshape(x_full.shape)

    nd = x.ndim
    w_specs = jax.tree_util.tree_map(
        lambda l: P("pod", *([None] * (l.ndim - 1))), w)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(*([None] * nd)), w_specs),
                       out_specs=P(*([None] * nd)),
                       check_vma=False)
    return fn(x, w).astype(x.dtype)
