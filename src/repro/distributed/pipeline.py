"""Pipeline parallelism over the 'pod' axis (GPipe-style, shard_map).

The multi-pod mesh exposes a 'pod' axis; by default it is an extra DP
axis, but `pipelined_apply` turns it into pipeline stages: each pod owns a
contiguous run of layers, microbatches stream through stages with
`jax.lax.ppermute` moving activations pod-to-pod.  The schedule is the
classic GPipe fill-drain loop implemented as a lax.scan over
(num_microbatches + num_stages - 1) ticks, so bubbles are explicit and
the collective is a single neighbour permute per tick — exactly what the
inter-pod DCI can sustain.

This module is deliberately self-contained (layer params stacked on a
leading 'stage' dim) and tested on a small host mesh; the production
launcher enables it with ModelConfig-agnostic stage_fn.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_stages(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous [start, end) layer ranges per stage (balanced)."""
    base, rem = divmod(n_layers, n_stages)
    out, start = [], 0
    for s in range(n_stages):
        size = base + (1 if s < rem else 0)
        out.append((start, start + size))
        start += size
    return out


def pipelined_apply(stage_params, x: jax.Array, stage_fn: Callable,
                    *, mesh: Mesh, axis: str = "pod",
                    num_microbatches: int) -> jax.Array:
    """Run x through all pipeline stages.

    Args:
      stage_params: pytree with leading dim = n_stages (sharded over axis).
      x: (B, ...) global batch; split into microbatches along dim 0.
      stage_fn: (params_for_stage, microbatch) -> microbatch output
        (same shape — standard homogeneous-stage pipeline).
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_microbatches == 0
    mb = b // num_microbatches
    micro = x.reshape(num_microbatches, mb, *x.shape[1:])

    def per_pod(params_local, micro_local):
        # params_local: stage_params for THIS pod (leading dim 1) ->
        # squeeze; micro_local: full microbatch stream (replicated).
        params_me = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        ticks = num_microbatches + n_stages - 1

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (if in range); others take buf.
            inject = jnp.where(t < num_microbatches,
                               jnp.clip(t, 0, num_microbatches - 1), 0)
            x_in = jnp.where(stage == 0, micro_local[inject], buf)
            active = (t - stage >= 0) & (t - stage < num_microbatches)
            y = stage_fn(params_me, x_in)
            y = jnp.where(active, y, buf)
            # pass to the next stage (ring; last stage's output wraps to 0
            # where it is ignored)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage writes its finished microbatch
            done_idx = t - (n_stages - 1)
            is_done = (stage == n_stages - 1) & (done_idx >= 0)
            outputs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], jnp.maximum(done_idx, 0), axis=0),
                lambda o: o, outputs)
            return (nxt, outputs), None

        buf0 = jnp.zeros_like(micro_local[0])
        outs0 = jnp.zeros_like(micro_local)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks))
        # Only the last stage holds real outputs; masked psum broadcasts
        # them so the result is replicated over the pipeline axis.
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    specs_params = jax.tree.map(lambda _: P(axis), stage_params)
    out = jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(specs_params, P()), out_specs=P(), check_vma=False,
    )(stage_params, micro)
    return out.reshape(b, *x.shape[1:])
