"""Sequence-sharded flash decode (§Perf, serve cells).

The baseline decode stores the KV cache with kv heads repeated to the TP
width (kv_eff = n_kv * repeat) so GSPMD can shard the head axis — 2x cache
HBM for kv=8 on a 16-way model axis, and the big-model serve cells miss
HBM (mistral-large decode_32k: 15.4 GB params + 11.8 GB cache > 16 GB).

This path stores the cache UNREPEATED (B, S, n_kv, D) and shards the
*sequence* axis over the model axis instead: each TP rank holds S/tp of
the cache, computes a partial flash (m, l, o) over its slice for ALL q
heads, and the partials merge with a logsumexp reduction (pmax + psum) —
the distributed equivalent of the flash-attention streaming softmax, and
structurally the TrIM psum-accumulation applied across chips.

Implemented as shard_map manual over the "model" axis, auto elsewhere
(batch stays GSPMD-sharded over the data axes). The single-token cache
write happens on the rank that owns the target position (predicated
dynamic-update-slice, no full-cache copy).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh_context

NEG_INF = -1e30


def _local_flash_decode(q, k_loc, v_loc, lo, pos, kv_length):
    """Partial flash over a local KV slice.

    q (B, n_kv, G, D) f32; k/v_loc (B, S_loc, n_kv, D); lo: global index of
    the slice start. Returns (o_unnorm (B,n_kv,G,D), m (B,n_kv,G), l)."""
    B, S_loc, n_kv, D = k_loc.shape
    s = jnp.einsum("bhgd,bshd->bhgs", q, k_loc.astype(jnp.float32))
    s = s * (D ** -0.5)
    cols = lo + jnp.arange(S_loc)
    limit = (pos + 1) if kv_length is None else kv_length
    if jnp.ndim(limit) == 0:
        mask = (cols < limit)[None, None, None, :]
    else:   # per-row lengths (B,)
        mask = cols[None, :] < limit[:, None]
        mask = mask[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_loc.astype(jnp.float32))
    return o, m, l


def _kv_len_array(B: int, pos, kv_length):
    if kv_length is None:
        return jnp.full((B,), pos + 1, jnp.int32)
    return kv_length.astype(jnp.int32)


def seqshard_flash_decode(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, new_k: jax.Array,
                          new_v: jax.Array, pos: jax.Array,
                          kv_length: Optional[jax.Array] = None,
                          axes: Tuple[str, ...] = ("model",),
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step against a sequence-sharded unrepeated cache.

    q (B, 1, n_q, D); k/v_cache (B, S, n_kv, D) sharded on dim 1 over
    `axes` (one or more mesh axes — the "2d" serve layout shards the
    sequence over ("data","model") with the batch replicated);
    new_k/v (B, 1, n_kv, D); pos scalar int32 (position written).
    Returns (o (B, 1, n_q, D), new k_cache, new v_cache).

    Without an active mesh (or without the axes) this runs the same math
    single-device — the oracle the distributed path is tested against.
    """
    B, _, n_q, D = q.shape
    n_kv = k_cache.shape[2]
    G = n_q // n_kv
    qg = q[:, 0].reshape(B, n_kv, G, D).astype(jnp.float32)

    kv_len = _kv_len_array(B, pos, kv_length)

    ctx = current_mesh_context()
    axes = tuple(a for a in axes
                 if ctx is not None and a in ctx.mesh.axis_names
                 and ctx.mesh.shape[a] > 1)
    if ctx is None or not axes:
        k_new = jax.lax.dynamic_update_slice_in_dim(
            k_cache, new_k.astype(k_cache.dtype), pos, axis=1)
        v_new = jax.lax.dynamic_update_slice_in_dim(
            v_cache, new_v.astype(v_cache.dtype), pos, axis=1)
        o, m, l = _local_flash_decode(qg, k_new, v_new, 0, pos, kv_len)
        out = (o / jnp.maximum(l, 1e-20)[..., None])
        return out.reshape(B, 1, n_q, D).astype(q.dtype), k_new, v_new

    mesh = ctx.mesh

    sizes = [mesh.shape[a] for a in axes]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(None, axes), P(None, axes), P(), P(), P(), P()),
        out_specs=(P(), P(None, axes), P(None, axes)),
        check_vma=False, axis_names=frozenset(axes))
    def body(qg, k_loc, v_loc, nk, nv, pos, kv_len):
        S_loc = k_loc.shape[1]
        idx = jnp.int32(0)                  # flattened over the axis tuple
        for a, s in zip(axes, sizes):
            idx = idx * s + jax.lax.axis_index(a)
        lo = idx * S_loc
        # predicated single-position write (no full-cache copy)
        loc = jnp.clip(pos - lo, 0, S_loc - 1)
        own = (pos >= lo) & (pos < lo + S_loc)
        old_k = jax.lax.dynamic_slice_in_dim(k_loc, loc, 1, axis=1)
        old_v = jax.lax.dynamic_slice_in_dim(v_loc, loc, 1, axis=1)
        k_w = jnp.where(own, nk.astype(k_loc.dtype), old_k)
        v_w = jnp.where(own, nv.astype(v_loc.dtype), old_v)
        k_loc = jax.lax.dynamic_update_slice_in_dim(k_loc, k_w, loc, axis=1)
        v_loc = jax.lax.dynamic_update_slice_in_dim(v_loc, v_w, loc, axis=1)
        o, m, l = _local_flash_decode(qg, k_loc, v_loc, lo, pos, kv_len)
        # distributed logsumexp merge
        m_g = jax.lax.pmax(m, axes)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, axes)
        o_g = jax.lax.psum(o * w[..., None], axes)
        out = o_g / jnp.maximum(l_g, 1e-20)[..., None]
        return out, k_loc, v_loc

    out, k_new, v_new = body(qg, k_cache, v_cache, new_k, new_v, pos,
                             kv_len)
    return (out.reshape(B, 1, n_q, D).astype(q.dtype), k_new, v_new)
