"""Plain reference of a chain CNN, for the configurations whose file
names ``"reference": "cnn"``: each conv layer feeds the next, and the
last map is flattened into a dense head.

Straightforward ``jax.numpy`` from the layer table in
``bench/configs/<config>.json``: ``lax.conv_general_dilated`` with the
table's stride, padding and tower groups, bias, ReLU, 2x2 max pools and
the dense head, all at ``Precision.HIGHEST`` in float32.  It imports
nothing of the program and takes nothing the program has made: the
weights are drawn again from the run's seed by the recipe the
configuration file states under ``init``.

``lane`` gives the lower-precision controls: ``"bf16"`` keeps every
operand and every layer's output in bfloat16 (products accumulate in
float32 inside the matrix unit), ``"int8"`` rounds both operands of
every product to int8 with one symmetric scale per tensor, which is what
an int8 lane would compute.  In a training step the int8 lane's
input-grad and weight-grad products take int8 operands too: the rounded
forward operands and the output's cotangent rounded the same way; the
gradient then reaches each unrounded operand straight through.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.work import head_dims, layer_groups

HI = lax.Precision.HIGHEST


def init_params(cfg: Dict[str, Any], seed: int):
    """Weights from the seed: one key split per layer, conv layers then
    the head; He-normal conv kernels, 1/sqrt(fan_in) head, zero biases."""
    key = jax.random.PRNGKey(seed)
    params = {"conv": [], "fc": []}
    for layer in cfg["layers"]:
        key, k = jax.random.split(key)
        shape = (layer["K"], layer["K"], layer["M"], layer["N"])
        std = math.sqrt(2.0 / (layer["K"] * layer["K"] * layer["M"]))
        params["conv"].append(
            {
                "kernel": jax.random.normal(k, shape, jnp.float32) * std,
                "bias": jnp.zeros((layer["N"],), jnp.float32),
            }
        )
    dims = head_dims(cfg)
    for i in range(len(dims) - 1):
        key, k = jax.random.split(key)
        w = jax.random.normal(k, (dims[i], dims[i + 1]), jnp.float32)
        params["fc"].append(
            {
                "kernel": w * dims[i] ** -0.5,
                "bias": jnp.zeros((dims[i + 1],), jnp.float32),
            }
        )
    return params


def _pool(x):
    b, h, w, c = x.shape
    x = x[:, : h // 2 * 2, : w // 2 * 2]
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def int8_round(t):
    """``t`` rounded to int8 steps of one symmetric per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 127.0
    return jnp.clip(jnp.round(t / s), -127, 127) * s


def int8_product(f):
    """The product ``f(x, w)`` with int8 operands both ways: the forward
    pass rounds ``x`` and ``w``, the backward pass runs the input-grad
    and weight-grad products on those and on the rounded cotangent."""

    @jax.custom_vjp
    def prod(x, w):
        return f(int8_round(x), int8_round(w))

    def fwd(x, w):
        xq, wq = int8_round(x), int8_round(w)
        return f(xq, wq), (xq, wq)

    def bwd(res, g):
        return jax.vjp(f, *res)[1](int8_round(g))

    prod.defvjp(fwd, bwd)
    return prod


LANES = {
    # lane: (stored dtype, product precision, accumulation, product wrapper)
    "f32": (jnp.float32, HI, jnp.float32, None),
    "bf16": (jnp.bfloat16, lax.Precision.DEFAULT, None, None),
    "int8": (jnp.float32, HI, jnp.float32, int8_product),
}


def check_program(cfg: Dict[str, Any], pcfg) -> None:
    """The program's entry ``pcfg`` (its ``CNNConfig``) has to be the
    chain the file states: input size, layer table, pools and head.
    Raises ``ValueError`` naming the first key that differs."""
    got = {
        "input_hw": list(pcfg.input_hw),
        "pool_after": list(pcfg.pool_after),
        "classifier": list(pcfg.classifier),
        "n_classes": pcfg.n_classes,
        "layers": [
            {
                "name": x.name,
                "H_I": x.H_I,
                "W_I": x.W_I,
                "K": x.K,
                "M": x.M,
                "N": x.N,
                "stride": x.stride,
                "pad": x.padding,
            }
            for x in pcfg.layers
        ],
    }
    for key, value in got.items():
        if cfg[key] != value:
            raise ValueError(f"{key} differs: the file has {cfg[key]}, the program {value}")


def check_config(cfg: Dict[str, Any]) -> None:
    """The reference pools 2x2 by max, with no LRN and no dropout."""
    if (cfg["pool"], cfg["lrn"], cfg["dropout"]) != ("max2x2", False, 0.0):
        raise ValueError(f"{cfg['name']}: the reference pools 2x2 by max, with no LRN and no dropout")


def forward(cfg: Dict[str, Any], params, images, lane: str = "f32"):
    """images (B, H, W, C) -> logits (B, n_classes), float32."""
    check_config(cfg)
    dtype, prec, acc, wrap = LANES[lane]
    wrap = wrap or (lambda f: f)
    x = images.astype(dtype)
    for i, (layer, g) in enumerate(zip(cfg["layers"], layer_groups(cfg))):
        p = params["conv"][i]
        conv = functools.partial(
            lax.conv_general_dilated,
            window_strides=(layer["stride"],) * 2,
            padding=[(layer["pad"], layer["pad"])] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=g,
            precision=prec,
            preferred_element_type=acc,
        )
        y = wrap(conv)(x, p["kernel"].astype(dtype))
        x = jnp.maximum(y.astype(jnp.float32) + p["bias"], 0.0).astype(dtype)
        if i in cfg["pool_after"]:
            x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    n = len(params["fc"])
    for j, p in enumerate(params["fc"]):
        dot = functools.partial(jnp.dot, precision=prec, preferred_element_type=acc)
        y = wrap(dot)(x, p["kernel"].astype(dtype))
        y = y.astype(jnp.float32) + p["bias"]
        x = (jnp.maximum(y, 0.0) if j < n - 1 else y).astype(dtype)
    return x.astype(jnp.float32)


def loss(cfg: Dict[str, Any], params, images, labels, lane: str = "f32"):
    """Mean softmax cross-entropy of the logits against the labels."""
    logits = forward(cfg, params, images, lane)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0].mean()
