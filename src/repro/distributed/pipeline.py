"""Optional pipeline parallelism over the "pod" axis (GPipe schedule).

At the assigned meshes (256/512 chips) every model fits with TP x DP + ZeRO,
so PP is OFF by default (DESIGN.md §6). For >2-pod scaling this module turns
the "pod" axis into a pipeline axis: each pod holds n_layers/PP contiguous
layers and microbatches flow stage-to-stage with ``lax.ppermute``.

Schedule: standard GPipe fill-drain over T = n_micro + PP - 1 ticks. At tick
t, stage s computes microbatch (t - s) if 0 <= t - s < n_micro. Bubble
fraction = (PP - 1) / T — reported by ``bubble_fraction``.

Implemented with shard_map manual over the pipeline axis; the stage body
stays in GSPMD auto mode over the remaining axes (so TP/DP still partition
each stage's compute).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_run(stage_fn: Callable[[Any, jax.Array], jax.Array],
                 stage_params: Any, x_micro: jax.Array, *, mesh: Mesh,
                 axis: str = "pod") -> jax.Array:
    """Run a GPipe pipeline over `axis`.

    stage_fn(params_for_stage, x) -> x  — one stage's layers.
    stage_params: pytree whose leaves have leading dim = n_stages.
    x_micro: (n_micro, mb, ...) microbatched activations (replicated over
    `axis`; stage 0 consumes them in order).
    Returns (n_micro, mb, ...) outputs (valid on the last stage, broadcast
    back to all).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1
    params_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        check_vma=False, axis_names=frozenset({axis}))
    def run(params, xs):
        # params leaves now have leading dim 1 (this stage's slice)
        params = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; others use the permuted buffer
            feed = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(sid == 0, xs[feed], buf)
            active = (t >= sid) & (t - sid < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            done_idx = t - (n_stages - 1)
            is_done = (sid == n_stages - 1) & (done_idx >= 0)
            outs = jax.lax.cond(
                is_done & (done_idx < n_micro),
                lambda o: o.at[jnp.maximum(done_idx, 0)].set(y),
                lambda o: o, outs)
            # shift activations to the next stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # broadcast the last stage's outputs to every stage (masked psum)
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run(stage_params, x_micro)
