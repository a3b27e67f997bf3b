"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For clusters where DPxTP doesn't reach the node count (or DCN bandwidth
makes FSDP all-gathers across pods too expensive), the block stack can be
split over a ``pipe`` mesh axis: each stage owns n_blocks/n_stages blocks;
microbatches flow stage-to-stage with ``jax.lax.ppermute``.

Schedule: GPipe (fill-drain).  With M microbatches and P stages the bubble
fraction is (P-1)/(M+P-1) — reported by :func:`bubble_fraction` so launch
configs can size M.  Forward-only here covers the serving/prefill case and
the structure of the comm pattern; training composes this with
jax.grad through the shard_map (exercised in tests at smoke scale).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(
    block_fn: Callable,  # (block_params, x) -> x
    stage_params,  # pytree stacked [n_blocks_total, ...], sharded on dim0
    x_micro,  # [n_micro, micro_batch, S, D] microbatched activations
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run the block stack as a pipeline over ``axis``.

    Each device holds n_blocks/P consecutive blocks (stage_params sharded on
    the stacked dim).  Returns the final activations [n_micro, mb, S, D].
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def stage(params_local, xs_local):
        # params_local: [blocks_per_stage, ...]; xs_local: all microbatches
        # (replicated across stages; only stage 0's input matters initially)
        idx = jax.lax.axis_index(axis)

        def run_blocks(x):
            def body(c, bp):
                return block_fn(bp, c), None

            out, _ = jax.lax.scan(body, x, params_local)
            return out

        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs_local[0])
        outs = jnp.zeros_like(xs_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid)
            mb = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(
                (idx == 0) & (t < n_micro), 1.0, 0.0
            )
            x_in = jnp.where(inject > 0, xs_local[mb], buf)
            y = run_blocks(x_in)
            # pass to the next stage (last stage's output wraps to 0 unused)
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage records finished microbatch (t - (n_stages - 1))
            done_mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = (idx == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(
                record,
                outs.at[done_mb].set(y),
                outs,
            )
            return (y_next, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(n_ticks)
        )
        # broadcast results from the last stage to everyone (masked psum —
        # ppermute needs unique destinations)
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    return jax.shard_map(
        stage,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
