"""Logical-axis sharding rules with divisibility fallback.

MaxText-style indirection: model code annotates activations/params with
*logical* axis names ("batch", "heads", "ff", ...); this module resolves
them against the active mesh using LOGICAL_RULES, picking the first mesh
axis (or axis tuple) whose size divides the dimension — falling back to
replication rather than erroring. That single rule-set makes all 12
architectures shardable on the production meshes without per-arch
special-casing (e.g. llava's 56 q-heads simply don't shard over model=16;
the fused q-projection output dim 7168 still does).

Inside jit-traced model code, ``shard(x, *axes)`` applies a
with_sharding_constraint when a MeshContext is active and is a no-op
otherwise (single-device tests).
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisCandidates = Tuple[Tuple[str, ...], ...]

#: logical axis -> ordered candidates (each candidate is a mesh-axis tuple).
#: First candidate whose total size divides the dim wins; else replicate.
LOGICAL_RULES: Dict[str, AxisCandidates] = {
    # data-parallel axes
    "batch": (("pod", "data"), ("data",), ("pod",)),
    "seq_shard": (("pod", "data"), ("data",)),     # sequence parallelism
    # tensor-parallel axes
    "vocab": (("model",),),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "ff": (("model",),),
    "qkv_dim": (("model",),),
    "d_inner": (("model",),),                       # mamba expanded dim
    "experts": (("model",),),
    "kv_seq": (("model",),),                        # seq-sharded decode KV
    "kv_seq2": (("data", "model"),),                # 2d serve layout
    "batch_pod": (("pod",),),                       # 2d serve: batch->pod
    # replicated axes
    "embed": (),
    "seq": (),
    "kv_len": (),
    "head_dim": (),
    "ssm_state": (),
    "conv_k": (),
    "layers": (),
    "capacity": (),
    # CNN path
    "img_h": (), "img_w": (),
    "cin": (), "cout": (("model",),),
}


@dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    rules: Dict[str, AxisCandidates] = field(default_factory=lambda: LOGICAL_RULES)
    extra: Dict[str, AxisCandidates] = field(default_factory=dict)

    def candidates(self, name: str) -> AxisCandidates:
        if name in self.extra:
            return self.extra[name]
        return self.rules.get(name, ())


_ACTIVE: ContextVar[Optional[MeshContext]] = ContextVar("mesh_ctx", default=None)


@contextmanager
def activate_mesh(mesh: Optional[Mesh],
                  extra_rules: Optional[Dict[str, AxisCandidates]] = None):
    """Make `mesh` the resolution target for shard()/logical_to_spec()."""
    ctx = None if mesh is None else MeshContext(mesh, extra=extra_rules or {})
    token = _ACTIVE.set(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.reset(token)


def current_mesh_context() -> Optional[MeshContext]:
    return _ACTIVE.get()


def _mesh_axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        if a not in mesh.axis_names:
            return 0  # candidate references an axis this mesh doesn't have
        size *= mesh.shape[a]
    return size


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    shape: Sequence[int],
                    ctx: Optional[MeshContext] = None) -> P:
    """Resolve logical axis names to a PartitionSpec for `shape`."""
    ctx = ctx or _ACTIVE.get()
    if ctx is None:
        return P()
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    spec = []
    used: set = set()
    for name, dim in zip(logical_axes, shape):
        entry = None
        if name is not None:
            for cand in ctx.candidates(name):
                size = _mesh_axis_size(ctx.mesh, cand)
                if size > 1 and dim % size == 0 and not (set(cand) & used):
                    entry = cand if len(cand) > 1 else cand[0]
                    used.update(cand)
                    break
        spec.append(entry)
    return P(*spec)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain an activation's sharding by logical axis names (no-op when
    no mesh is active)."""
    ctx = _ACTIVE.get()
    if ctx is None:
        return x
    spec = logical_to_spec(logical_axes, x.shape, ctx)
    return lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding: path-pattern -> logical axes
# ---------------------------------------------------------------------------

#: Parameter-path regex -> logical axes per dim (applied to the *trailing*
#: dims; leading scan/stack dims resolve to None). First match wins.
PARAM_AXIS_PATTERNS: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed/table", ("vocab", "embed")),
    (r"lm_head/kernel", ("embed", "vocab")),
    (r"(q_proj|k_proj|v_proj)/kernel", ("embed", "qkv_dim")),
    (r"o_proj/kernel", ("qkv_dim", "embed")),
    (r"experts/w_(gate|up)", ("experts", "embed", "ff")),
    (r"experts/w_down", ("experts", "ff", "embed")),
    (r"router/kernel", ("embed", None)),
    (r"(mlp|shared_expert|dense_mlp)/w_(gate|up)/kernel", ("embed", "ff")),
    (r"(mlp|shared_expert|dense_mlp)/w_down/kernel", ("ff", "embed")),
    (r"mlp/w_in/kernel", ("embed", "ff")),
    (r"mlp/w_out/kernel", ("ff", "embed")),
    (r"in_proj/kernel", ("embed", "d_inner")),
    (r"out_proj/kernel", ("d_inner", "embed")),
    (r"conv1d/w", ("conv_k", "d_inner")),
    (r"(A_log|dt_bias|D)$", ("d_inner",)),
    (r"ssm_norm/scale", ("d_inner",)),
    # ConvNet params live in a list: conv/<layer-idx>/kernel.
    (r"conv/(\d+/)?kernel", ("conv_k", "conv_k", "cin", "cout")),
    (r"(norm|ln)[^/]*/(scale|bias)", ("embed",)),
    (r"bias$", (None,)),
)


def param_logical_axes(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Logical axes for a parameter, by path pattern (trailing-dim aligned)."""
    for pat, axes in PARAM_AXIS_PATTERNS:
        if re.search(pat, path):
            if len(axes) > ndim:
                axes = axes[len(axes) - ndim:]
            return (None,) * (ndim - len(axes)) + tuple(axes)
    return (None,) * ndim


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspec(params, ctx: Optional[MeshContext] = None):
    """PartitionSpec tree for a parameter pytree (by path patterns)."""
    ctx = ctx or _ACTIVE.get()

    def one(path, leaf):
        axes = param_logical_axes(_path_str(path), np.ndim(leaf))
        return logical_to_spec(axes, np.shape(leaf), ctx)

    return jax.tree_util.tree_map_with_path(one, params)


def _dp_extend(spec, shape, ctx, dp_axes):
    """Shard the largest still-unsharded dim over the data axes."""
    spec = list(spec) + [None] * (len(shape) - len(spec))
    if ctx is None:
        return P(*spec)
    avail = tuple(a for a in dp_axes if a in ctx.mesh.axis_names)
    size = int(np.prod([ctx.mesh.shape[a] for a in avail])) if avail else 0
    if size > 1:
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for d in dims:
            if spec[d] is None and shape[d] % size == 0:
                spec[d] = avail if len(avail) > 1 else avail[0]
                break
    return P(*spec)


def zero1_pspec(params, ctx: Optional[MeshContext] = None,
                dp_axes: Tuple[str, ...] = ("pod", "data")):
    """ZeRO-1 spec for optimizer state: param spec + shard the largest
    still-unsharded dim over the data axes (divisibility permitting)."""
    ctx = ctx or _ACTIVE.get()

    def one(path, leaf):
        axes = param_logical_axes(_path_str(path), np.ndim(leaf))
        spec = logical_to_spec(axes, np.shape(leaf), ctx)
        return _dp_extend(spec, np.shape(leaf), ctx, dp_axes)

    return jax.tree_util.tree_map_with_path(one, params)


def fsdp_pspec(params, ctx: Optional[MeshContext] = None,
               dp_axes: Tuple[str, ...] = ("pod", "data")):
    """FSDP/ZeRO-3-style PARAMETER sharding: on top of the TP assignment,
    the largest remaining dim of every weight shards over the data axes.
    GSPMD inserts the per-layer weight all-gathers (and reduce-scatters on
    the gradients) automatically — HBM for resident params drops by the
    DP world size, traded against the collective term (measured in
    §Perf). This is what lets arctic-480b / llama4 / mistral-large fit a
    16 GB/chip pod (§Roofline fits_hbm)."""
    return zero1_pspec(params, ctx, dp_axes)
