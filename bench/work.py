"""The work each layer needs, from the configuration's published shapes.

Counts come from the unpadded layer table in ``bench/configs/*.json``,
never from the folded or padded geometry a kernel runs, so no change to
the program can move them.  FLOPs are 2 x MACs.  A layer's bytes are its
input, weights and output, each moved once, at the lane's element size.
Training counts the forward pass, the weight gradient of every layer and
the input gradient of every layer but the first.
"""

from __future__ import annotations

from typing import Any, Dict, List


def _hw_out(layer: Dict[str, int]) -> int:
    return (layer["H_I"] + 2 * layer["pad"] - layer["K"]) // layer["stride"] + 1


def conv_layers(cfg: Dict[str, Any]) -> List[Dict[str, int]]:
    """Per conv layer: MACs, and input, weight and output elements, per
    image (weights once)."""
    out, c = [], cfg["in_channels"]
    for layer in cfg["layers"]:
        ho = _hw_out(layer)
        out.append(
            {
                "name": layer["name"],
                "macs": ho * ho * layer["K"] ** 2 * layer["M"] * layer["N"],
                "in": layer["H_I"] * layer["W_I"] * c,
                "w": layer["K"] ** 2 * layer["M"] * layer["N"],
                "out": ho * ho * layer["N"],
            }
        )
        c = layer["N"]
    return out


def head_dims(cfg: Dict[str, Any]) -> List[int]:
    """Widths of the dense head, the flattened feature map first."""
    hw = None
    for i, layer in enumerate(cfg["layers"]):
        hw = _hw_out(layer)
        if i in cfg["pool_after"]:
            hw //= 2
    return [hw * hw * cfg["layers"][-1]["N"]] + list(cfg["classifier"]) + [cfg["n_classes"]]


def conv_macs(cfg) -> int:
    return sum(x["macs"] for x in conv_layers(cfg))


def head_macs(cfg) -> int:
    d = head_dims(cfg)
    return sum(a * b for a, b in zip(d[:-1], d[1:]))


def head_weight_bytes(cfg, elem_bytes: int = 4) -> int:
    """The head's weights and biases."""
    d = head_dims(cfg)
    return elem_bytes * sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def forward_flops(cfg) -> int:
    """FLOPs of one image's forward pass."""
    return 2 * (conv_macs(cfg) + head_macs(cfg))


def train_flops(cfg) -> int:
    """FLOPs of one image's training step: forward, weight grads of every
    layer, input grads of every layer but the first."""
    first = conv_layers(cfg)[0]["macs"]
    return 2 * (3 * conv_macs(cfg) - first + 3 * head_macs(cfg))


def conv_min_s(cfg, images: int, peaks: Dict[str, float], elem_bytes: int = 4) -> float:
    """Least time the chip could take for one pass (forward, input grad
    or weight grad: the same operations and bytes) of every conv layer
    over ``images`` images: per layer the larger of operations over peak
    and bytes over bandwidth."""
    total = 0.0
    for x in conv_layers(cfg):
        flops = 2 * x["macs"] * images
        nbytes = elem_bytes * (images * (x["in"] + x["out"]) + x["w"])
        total += max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return total


def head_min_s(cfg, images: int, peaks: Dict[str, float], elem_bytes: int = 4) -> float:
    """Least time for the head over one batch of ``images``: its weights
    read once, against its operations at peak."""
    d = head_dims(cfg)
    flops = 2 * head_macs(cfg) * images
    nbytes = head_weight_bytes(cfg, elem_bytes) + elem_bytes * images * sum(d)
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
