"""GPipe-style pipeline parallelism over a mesh "stage" axis via shard_map.

Optional at 512 chips (DP×TP is optimal for the assigned sizes — see
EXPERIMENTS.md §Roofline), provided for scale-out past HBM limits at 1000+
nodes.  The schedule is the classic GPipe loop: with S stages and M
microbatches the bubble fraction is (S-1)/(M+S-1); activations move between
stages with `jax.lax.ppermute` (ICI neighbor exchange).

The layer stack [L, ...] is split into S contiguous stages of L/S layers;
each stage device scans its slice.  Works with any per-layer body of the
form body(layer_params, x) -> x.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(body: Callable, mesh: Mesh, *, stage_axis: str = "stage",
                   n_microbatches: int):
    """Returns fn(stacked_params, x) running the stack as a pipeline.

    stacked_params leaves: [L, ...] with L % n_stages == 0.
    x: [B, ...] with B % n_microbatches == 0.
    """
    n_stages = mesh.shape[stage_axis]

    def stage_fn(params_slice, x_mb):
        """Runs on ONE stage (inside shard_map): scan over local layers."""
        def scan_body(h, lp):
            return body(lp, h), None

        # local params have a leading [L/S] dim (stage dim mapped away)
        h, _ = jax.lax.scan(scan_body, x_mb, params_slice)
        return h

    def pipelined(params, x):
        stage_id = jax.lax.axis_index(stage_axis)
        mbs = x.reshape(n_microbatches, -1, *x.shape[1:])
        n_ticks = n_microbatches + n_stages - 1
        buf = jnp.zeros_like(mbs[0])
        outputs = jnp.zeros_like(mbs)

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t (if in range)
            inject = jnp.where(t < n_microbatches, t, n_microbatches - 1)
            x_in = jnp.where(stage_id == 0, mbs[inject], buf)
            y = stage_fn(params, x_in)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            shifted = jax.lax.ppermute(y, stage_axis, perm)
            # last stage emits microbatch t - (S-1)
            emit_idx = t - (n_stages - 1)
            valid = (emit_idx >= 0) & (emit_idx < n_microbatches)
            idx = jnp.clip(emit_idx, 0, n_microbatches - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: o.at[idx].set(
                    jnp.where(stage_id == n_stages - 1, y, o[idx])),
                lambda o: o, outputs)
            return (shifted, outputs), None

        (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                         jnp.arange(n_ticks))
        # broadcast the final outputs from the last stage to all stages
        # (psum of a masked copy — ppermute can't fan out one source)
        mask = (jax.lax.axis_index(stage_axis) == n_stages - 1)
        outputs = jax.lax.psum(
            jnp.where(mask, outputs, jnp.zeros_like(outputs)), stage_axis)
        return outputs.reshape(-1, *outputs.shape[2:])

    in_specs = (P(stage_axis), P())
    out_specs = P()
    return shard_map(pipelined, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)
