"""The work each layer needs, from the configuration's published shapes.

Counts come from the unpadded layer table in ``bench/configs/*.json``,
never from the folded or padded geometry a kernel runs, so no change to
the program can move them.  FLOPs are 2 x MACs.  A layer's bytes are its
input, weights and output, each moved once, at the lane's element size.
Training counts the forward pass, the weight gradient of every layer and
the input gradient of every layer but the first.

Two keys of a configuration file let a model that is not a chain state
what the chain would derive wrongly:

- a layer's ``"groups"``: its input is ``H_I x W_I x M x groups``.
  Without it the groups are the running channel count (the previous
  layer's ``N``, the image's channels first) over ``M``, which gives
  AlexNet's towers 2 and every other chain layer 1; a layer fed from
  elsewhere than the previous one, such as a projection shortcut,
  states it;
- ``"head_in"``: the width that enters the dense head.  Without it the
  last layer's map is flattened after halving it at each ``pool_after``;
  after a global average pool it is the last layer's ``N``.
"""

from __future__ import annotations

from typing import Any, Dict, List


def _hw_out(layer: Dict[str, int]) -> int:
    return (layer["H_I"] + 2 * layer["pad"] - layer["K"]) // layer["stride"] + 1


def layer_groups(cfg: Dict[str, Any]) -> List[int]:
    """Per conv layer, its ``"groups"``, or the running channel count
    over its input channels where the table gives none."""
    out, c = [], cfg["in_channels"]
    for layer in cfg["layers"]:
        out.append(layer.get("groups", c // layer["M"]))
        c = layer["N"]
    return out


def conv_layers(cfg: Dict[str, Any]) -> List[Dict[str, int]]:
    """Per conv layer: MACs, and input, weight and output elements, per
    image (weights once)."""
    out = []
    for layer, groups in zip(cfg["layers"], layer_groups(cfg)):
        ho = _hw_out(layer)
        out.append(
            {
                "name": layer["name"],
                "macs": ho * ho * layer["K"] ** 2 * layer["M"] * layer["N"],
                "in": layer["H_I"] * layer["W_I"] * layer["M"] * groups,
                "w": layer["K"] ** 2 * layer["M"] * layer["N"],
                "out": ho * ho * layer["N"],
            }
        )
    return out


def head_dims(cfg: Dict[str, Any]) -> List[int]:
    """Widths of the dense head, its input first: ``"head_in"``, or the
    flattened feature map."""
    if "head_in" in cfg:
        first = cfg["head_in"]
    else:
        hw = None
        for i, layer in enumerate(cfg["layers"]):
            hw = _hw_out(layer)
            if i in cfg["pool_after"]:
                hw //= 2
        first = hw * hw * cfg["layers"][-1]["N"]
    return [first] + list(cfg["classifier"]) + [cfg["n_classes"]]


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
