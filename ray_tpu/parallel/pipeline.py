"""Pipeline parallelism over the ``stage`` mesh axis.

The reference expresses pipeline stages as compiled-DAG nodes with NCCL
channels between GPU actors (SURVEY.md §2.3 aDAG). TPU-native, a pipeline is
ONE jitted SPMD program: layers are sharded onto the ``stage`` mesh axis and
microbatch activations flow between adjacent stages with
``jax.lax.ppermute`` (nearest-neighbor ICI hops) inside a ``lax.scan`` —
GPipe-style fill/drain, no host round-trips per microbatch.

``pipelined`` wraps a per-stage apply function; layers for all stages are
stacked on a leading axis so each stage reads its own slab via shard_map.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def pipelined(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    *,
    num_microbatches: int,
    axis_name: str = "stage",
) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
    """Build a pipelined forward: y = stageN(...stage1(x)).

    ``stage_fn(stage_params, x_mb)`` applies ONE stage to one microbatch.
    Returned callable takes (stacked_stage_params, batch) where
    ``stacked_stage_params`` has a leading stage axis sharded over
    ``axis_name`` and ``batch`` is [B, ...] with B divisible by
    ``num_microbatches``.

    Schedule: classic GPipe loop of length M + S - 1. At step t, the device
    holding stage s processes microbatch (t - s); activations ppermute one
    hop toward stage s+1 each step. Bubble fraction = (S-1)/(M+S-1).
    """
    num_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    def run(stage_params, batch):
        mb = jnp.reshape(batch, (num_microbatches, -1) + batch.shape[1:])

        def body(local_params, mb_local):
            # mb_local: [M, b_local, ...] replicated view per stage device.
            stage_idx = jax.lax.axis_index(axis_name)
            steps = num_microbatches + num_stages - 1
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

            local_params = jax.tree.map(lambda p: p[0], local_params)
            out_buf = jnp.zeros_like(mb_local)
            carry = jnp.zeros_like(mb_local[0])

            def step(state, t):
                carry, out_buf = state
                # Stage 0 ingests microbatch t; others use the carried
                # activation that just arrived from the previous stage.
                mb_idx = jnp.clip(t, 0, num_microbatches - 1)
                x_in = jnp.where(stage_idx == 0, mb_local[mb_idx], carry)
                y = stage_fn(local_params, x_in)
                # Valid only while this stage has a real microbatch in hand.
                my_mb = t - stage_idx
                valid = (my_mb >= 0) & (my_mb < num_microbatches)
                y = jnp.where(valid, y, jnp.zeros_like(y))
                # Last stage banks its finished microbatch.
                finished = valid & (stage_idx == num_stages - 1)
                slot = jnp.clip(my_mb, 0, num_microbatches - 1)
                out_buf = jax.lax.cond(
                    finished,
                    lambda b: b.at[slot].set(y),
                    lambda b: b,
                    out_buf)
                # Ship activations one hop down the pipeline.
                carry = jax.lax.ppermute(y, axis_name, perm)
                return (carry, out_buf), None

            (carry, out_buf), _ = jax.lax.scan(
                step, (carry, out_buf), jnp.arange(steps))
            # Only the last stage's buffer is real; psum of the masked buffer
            # replicates it across the stage axis (ppermute cannot broadcast
            # one source to many destinations).
            last = num_stages - 1
            masked = jnp.where(stage_idx == last, out_buf,
                               jnp.zeros_like(out_buf))
            return jax.lax.psum(masked, axis_name)

        spec_params = jax.tree.map(lambda _: P(axis_name), stage_params)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec_params, P()),
            out_specs=P(),
            check_vma=False,
        )(stage_params, mb)
        return out.reshape((-1,) + out.shape[2:])

    return run
