"""Plain reference of a toy residual CNN, which a test adds to a
benchmark tree as a file of its own.

Each conv layer reads the output its ``"from"`` names (``"image"`` for
the input), adds the output its ``"add"`` names before the ReLU, and
leaves the ReLU out where it states ``"relu": false``.  A global average
pool of the last layer's map feeds the dense head, ``"head_in"`` wide.
The lanes are those of the chain reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.cnn import LANES


def init_params(cfg, seed):
    key = jax.random.PRNGKey(seed)
    params = {"conv": [], "fc": []}
    for layer in cfg["layers"]:
        key, k = jax.random.split(key)
        shape = (layer["K"], layer["K"], layer["M"], layer["N"])
        std = math.sqrt(2.0 / (layer["K"] * layer["K"] * layer["M"]))
        params["conv"].append(
            {"kernel": jax.random.normal(k, shape, jnp.float32) * std, "bias": jnp.zeros((layer["N"],), jnp.float32)}
        )
    dims = [cfg["head_in"]] + list(cfg["classifier"]) + [cfg["n_classes"]]
    for a, b in zip(dims[:-1], dims[1:]):
        key, k = jax.random.split(key)
        params["fc"].append(
            {"kernel": jax.random.normal(k, (a, b), jnp.float32) * a**-0.5, "bias": jnp.zeros((b,), jnp.float32)}
        )
    return params


def forward(cfg, params, images, lane="f32"):
    dtype, prec, acc, wrap = LANES[lane]
    wrap = wrap or (lambda f: f)
    outs = {"image": images.astype(dtype)}
    for layer, p in zip(cfg["layers"], params["conv"]):

        def conv(x, w, layer=layer):
            return lax.conv_general_dilated(
                x,
                w,
                window_strides=(layer["stride"],) * 2,
                padding=[(layer["pad"], layer["pad"])] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=layer["groups"],
                precision=prec,
                preferred_element_type=acc,
            )

        y = wrap(conv)(outs[layer["from"]], p["kernel"].astype(dtype)).astype(jnp.float32) + p["bias"]
        if "add" in layer:
            y = y + outs[layer["add"]].astype(jnp.float32)
        if layer.get("relu", True):
            y = jnp.maximum(y, 0.0)
        outs[layer["name"]] = y.astype(dtype)
    x = outs[cfg["layers"][-1]["name"]].astype(jnp.float32).mean(axis=(1, 2)).astype(dtype)
    for j, p in enumerate(params["fc"]):
        y = wrap(lambda a, b: jnp.dot(a, b, precision=prec, preferred_element_type=acc))(x, p["kernel"].astype(dtype))
        y = y.astype(jnp.float32) + p["bias"]
        x = (jnp.maximum(y, 0.0) if j < len(params["fc"]) - 1 else y).astype(dtype)
    return x.astype(jnp.float32)


def loss(cfg, params, images, labels, lane="f32"):
    logp = jax.nn.log_softmax(forward(cfg, params, images, lane), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0].mean()


def check_program(cfg, pcfg):
    """The program's entry would have to describe the shortcuts; its
    ``CNNConfig`` is a chain."""
    if not hasattr(pcfg, "blocks"):
        raise ValueError(f"{cfg['name']} has shortcuts; the program's {type(pcfg).__name__} is a chain")
