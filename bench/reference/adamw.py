"""Plain reference of the training cells' optimizer, from the cell's
``optimizer`` block: the global gradient norm clipped to ``clip_norm``,
AdamW with bias correction and decoupled weight decay (not on biases),
and a learning rate that warms up linearly for ``warmup_steps`` steps
and then follows a cosine down to ``min_ratio`` of its peak."""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def learning_rate(opt: Dict[str, Any], step: int) -> float:
    """The rate of the step that starts with ``step`` steps done."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1) / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    ratio = opt["min_ratio"]
    return peak * (ratio + (1 - ratio) * 0.5 * (1 + math.cos(math.pi * frac)))


def clip(opt: Dict[str, Any], grads):
    """Gradients scaled so that their global norm is at most
    ``clip_norm``; this is what the optimizer's moments see."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def update(opt: Dict[str, Any], params, grads, m, v, step: int, lr: float):
    """One AdamW step from ``step`` steps done, with clipped ``grads``;
    returns (params, m, v)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    c1 = 1.0 - b1 ** (step + 1)
    c2 = 1.0 - b2 ** (step + 1)
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree.structure(params)
    out_p, out_m, out_v = [], [], []
    for (path, p), g, mi, vi in zip(paths, jax.tree.leaves(grads), jax.tree.leaves(m), jax.tree.leaves(v)):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        u = (mi / c1) / (jnp.sqrt(vi / c2) + eps)
        if not any(f in name for f in opt["no_decay"]):
            u = u + wd * p
        out_p.append(p - lr * u)
        out_m.append(mi)
        out_v.append(vi)
    return tuple(jax.tree.unflatten(treedef, x) for x in (out_p, out_m, out_v))
