"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run locks the device count via XLA_FLAGS
*before* any jax initialization).

Mesh geometry (DESIGN.md §6):
- single-pod: (16, 16) over ("data", "model") — 256 chips (one v5e pod).
- multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.
  The "pod" axis carries data parallelism by default (batch shards over
  ("pod", "data")); ``distributed.pipeline`` can repurpose it as a
  pipeline axis for >2-pod scaling.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axes: the sharding rules here place
    arrays with ``with_sharding_constraint`` / jit shardings, which
    ``jax.make_mesh``'s default Explicit axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None):
    """Small mesh over however many (host) devices exist — used by the
    launchers, tests and the smoke examples."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


#: v5e hardware constants used by the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 5.0e10                # bytes/s per link direction (~50 GB/s)
HBM_BYTES = 16 * 2 ** 30       # 16 GiB HBM per v5e chip
