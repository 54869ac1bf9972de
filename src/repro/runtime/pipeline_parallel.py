"""Pipeline parallelism over the 'pod' axis (GPipe schedule, shard_map).

At multi-pod scale the inter-pod DCN link is much slower than ICI; pipelining
layer stages across pods moves only the (B_micro, S, D) activation per tick
instead of synchronizing gradients for the whole model. This module provides
the forward GPipe schedule used for pipelined inference / as the building
block for interleaved training schedules:

  * stage s holds layers [s*L/P, (s+1)*L/P)  (params sharded over 'pod')
  * microbatches flow stage->stage via collective_permute (ppermute)
  * total ticks = n_micro + n_stages - 1 (the usual bubble)

All devices execute every tick (SPMD); off-schedule stages compute on garbage
and their results are masked — the standard single-program formulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, stage_params, x, mesh, axis: str = "pod", n_micro: int | None = None):
    """Run ``y = stage_{P-1}(...stage_0(x))`` pipelined over ``axis``.

    stage_fn(params_slice, h) -> h   (one stage's computation)
    stage_params: pytree with leading dim = n_stages (sharded over `axis`)
    x: (n_micro, B_micro, ...) microbatched input (replicated over `axis`)
    Returns y with x's shape.
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or x.shape[0]
    assert x.shape[0] == n_micro

    def body(params_local, xs):
        # params_local: leading dim 1 (this stage's slice); xs: full microbatches
        params_me = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        h0 = jnp.zeros_like(xs[0])
        out = jnp.zeros_like(xs)

        def tick(carry, t):
            recv, out = carry
            # stage 0 ingests microbatch t (when on schedule)
            mb_in = jax.lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            h = jnp.where(stage == 0, mb_in, recv)
            h = stage_fn(params_me, h)
            # last stage emits microbatch t - (n_stages - 1)
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(out, emit_idx, axis=0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(emit, h, cur), emit_idx, axis=0
            )
            nxt = jax.lax.ppermute(h, axis, perm)
            return (nxt, out), None

        (_, out), _ = jax.lax.scan(tick, (h0, out), jnp.arange(n_ticks))
        # only the last stage holds valid outputs; broadcast them to all stages
        out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis)

    other = tuple(a for a in mesh.axis_names if a != axis)
    del other
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def stack_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/P, ...) stage-stacked."""
    def re(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree.map(re, layer_params)
