"""int8-compressed data-parallel gradient reduction with error feedback.

Wire format per leaf: bf16 reduce-scatter (the summation must stay high
precision) followed by an **int8 all-gather** of the reduced shard plus one
f32 scale — 2B + 1B ≈ 3B/element on the wire vs 8B for a plain f32
all-reduce (the ~2.7x saving quoted in DESIGN.md §6). Quantization error is
carried in an error-feedback accumulator folded into the *next* step's
gradient (Karimireddy et al. 2019), which keeps SGD/Adam convergence
unbiased to first order.

Implementation: ``shard_map`` manual over the DP axes with ``auto`` over
the remaining axes — tensor-parallel partitioning inside the body is still
GSPMD's job, only the data-parallel reduction is taken over manually.
Leaves whose leading dim does not divide the DP world size fall back to a
plain bf16 psum (counted, not hidden).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

DP_AXES = ("pod", "data")


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def _wire_dtype():
    """bf16 reduce on TPU; f32 on CPU (XLA CPU cannot promote bf16
    all-reduce — the *format* is unchanged, only the CI-runnable dtype)."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def int8_psum(g: jax.Array, axes: Tuple[str, ...],
              ef: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """all-reduce(g) over `axes` with the compressed wire format
    (bf16 reduce-scatter + int8 all-gather), with optional error-feedback
    shard `ef` (the local reduce-scattered residual from the previous
    step). Caller guarantees dim 0 divides the DP world size.

    Returns (reduced g, new ef shard or None)."""
    gf = g.astype(_wire_dtype())
    # reduce-scatter over the (flattened) DP axes, tiled on dim 0
    rs = gf
    for ax in axes:
        rs = jax.lax.psum_scatter(rs, ax, scatter_dimension=0, tiled=True)
    rs = rs.astype(jnp.float32)
    if ef is not None:
        rs = rs + ef
    # int8 quantize the reduced shard
    scale = jnp.maximum(jnp.max(jnp.abs(rs)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(rs / scale), -127, 127).astype(jnp.int8)
    new_ef = rs - q.astype(jnp.float32) * scale if ef is not None else None
    # all-gather shards back (int8 + f32 scale on the wire)
    out = q
    scales = scale[None]
    for ax in reversed(axes):
        out = jax.lax.all_gather(out, ax, axis=0, tiled=True)
        scales = jax.lax.all_gather(scales, ax, axis=0, tiled=True)
    # per-shard dequant: shard i occupies rows [i*lead/world, ...)
    n_shards = scales.shape[0]
    out = out.reshape((n_shards, out.shape[0] // n_shards) + out.shape[1:])
    deq = out.astype(jnp.float32) * scales.reshape(
        (n_shards,) + (1,) * (out.ndim - 1))
    return deq.reshape((-1,) + deq.shape[2:]), new_ef


def _compressible(g, world: int) -> bool:
    return g.ndim >= 1 and g.shape[0] % world == 0 and g.shape[0] >= world


def _reduce_leaf(g: jax.Array, ef: Optional[jax.Array],
                 axes: Tuple[str, ...], world: int):
    if _compressible(g, world):
        return int8_psum(g, axes, ef)
    # fallback: plain bf16 all-reduce (small leaves: norms, biases)
    return (jax.lax.psum(g.astype(_wire_dtype()), axes).astype(jnp.float32),
            ef)


def init_ef(params, mesh: Mesh):
    """Error-feedback accumulator tree: zeros shaped like each compressible
    grad's reduce-scattered shard, f32, sharded over the DP axes on dim 0
    (non-compressible leaves get a zero scalar placeholder)."""
    axes = _dp_axes(mesh)
    world = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def one(p):
        if axes and _compressible(p, world):
            return jnp.zeros(p.shape, jnp.float32)
        return jnp.zeros((), jnp.float32)
    return jax.tree.map(one, params)


def compressed_grads(loss_fn: Callable, params, batch, mesh: Mesh,
                     ef=None):
    """value_and_grad with manual compressed DP reduction.

    loss_fn(params, batch) -> (loss, aux_dict). The DP axes are manual
    (shard_map); everything else stays in GSPMD auto mode.
    Returns ((loss, {}), grads) or ((loss, {}), grads, new_ef) when an
    error-feedback tree is supplied.
    """
    axes = _dp_axes(mesh)
    if not axes:
        out = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return out if ef is None else (*out, ef)
    world = int(np.prod([mesh.shape[a] for a in axes]))

    batch_spec = jax.tree.map(lambda _: P(axes), batch)
    param_spec = jax.tree.map(lambda _: P(), params)
    has_ef = ef is not None
    ef_spec = jax.tree.map(
        lambda e: P(axes) if e.ndim else P(),
        ef) if has_ef else jax.tree.map(lambda _: P(), params)
    ef_in = ef if has_ef else params  # placeholder tree (unused)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_spec, batch_spec, ef_spec),
        out_specs=(P(), param_spec, ef_spec),
        check_vma=False, axis_names=frozenset(axes))
    def body(p, b, e):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        loss = jax.lax.pmean(loss.astype(jnp.float32), axes)

        def leaf(gl, el):
            red, ne = _reduce_leaf(gl, el if has_ef and el.ndim else None,
                                   axes, world)
            return red / world, (ne if ne is not None else el)
        pairs = jax.tree.map(leaf, g, e)
        treedef = jax.tree_util.tree_structure(g)
        flat = treedef.flatten_up_to(pairs)
        g_out = treedef.unflatten([f[0] for f in flat])
        e_out = treedef.unflatten([f[1] for f in flat])
        return loss, g_out, e_out

    loss, grads, new_ef = body(params, batch, ef_in)
    if has_ef:
        return (loss, {}), grads, new_ef
    return (loss, {}), grads
