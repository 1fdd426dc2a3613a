"""Pipeline parallelism: GPipe schedule over a 1D "pipe" mesh axis.

Each device owns one stage's weights; microbatches stream through the
stages, with activations handed to the next stage via collective-permute.
With M microbatches and P stages the schedule runs M+P-1 ticks, so the
bubble (idle) fraction is (P-1)/(M+P-1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(microbatches: int, stages: int) -> float:
    """Idle fraction of the GPipe schedule: (P-1)/(M+P-1)."""
    return (stages - 1) / (microbatches + stages - 1)


def pipeline_apply(stage_fn, stage_params: jax.Array, x: jax.Array, *,
                   mesh, axis: str) -> jax.Array:
    """Run x through P stages, stage p resident on device p of ``axis``.

    stage_fn: (W, h) -> h' applied per microbatch.
    stage_params: [P, ...] per-stage weights (sharded over ``axis``).
    x: [M, microbatch, ...] microbatches (replicated).
    Returns [M, microbatch, ...] after all P stages, replicated.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(w_local, x_full):
        w = w_local[0]
        p = jax.lax.axis_index(axis)
        # the carry differs per stage once the first tick has run
        recv, outs = jax.lax.pcast(
            (jnp.zeros(x_full.shape[1:], x_full.dtype),
             jnp.zeros_like(x_full)), (axis,), to="varying")

        def tick(carry, t):
            recv, outs = carry
            # stage 0 ingests microbatch t; later stages consume the
            # activation permuted in from stage p-1 at tick t-1
            h_in = jnp.where(
                p == 0, x_full[jnp.clip(t, 0, n_micro - 1)], recv
            )
            h_out = stage_fn(w, h_in)
            o_idx = t - (n_stages - 1)
            valid = jnp.logical_and(p == n_stages - 1, o_idx >= 0)
            written = outs.at[jnp.clip(o_idx, 0, n_micro - 1)].set(h_out)
            outs = jnp.where(valid, written, outs)
            nxt = jax.lax.ppermute(h_out, axis, perm)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (recv, outs), jnp.arange(n_micro + n_stages - 1)
        )
        # only the last stage holds real outputs (others kept zeros):
        # a psum broadcasts them so the result is replicated
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
    )(stage_params, x)
