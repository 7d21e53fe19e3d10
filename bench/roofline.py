"""Kernel time from the reduced trace against the least time the chip
could take for the same work (``work.py``), for the roofline readers.

In a v5e trace each operation is named by its HLO text.  The conv
kernels are the program's only Mosaic calls (forward, input grad and
weight grad): ``custom_call_target="tpu_custom_call"``.  A share is
the least time over the measured time: it cannot pass 100% unless the
work is counted too high or part of the time is missing.
"""

from __future__ import annotations

from bench.work import conv_min_s

MOSAIC = 'custom_call_target="tpu_custom_call"'


def is_conv_kernel(op) -> bool:
    return MOSAIC in op.name


def conv_kernel_s(run) -> float:
    return run.reduced.seconds(is_conv_kernel) if run.reduced is not None else 0.0


def serve_conv_share(run):
    """Conv kernels' roofline share over the window's batches: every
    flushed bucket is run whole, padding slots included."""
    flushes = run.obs.get("flushes")
    t = conv_kernel_s(run)
    if not flushes or t <= 0:
        return None
    need = sum(n * conv_min_s(run.cfg, b, run.peaks) for b, n in flushes.items())
    return 100.0 * need / t


def train_conv_share(run):
    """Conv kernels' roofline share over the window's steps: per chip and
    step, a forward and a weight-grad pass of every layer and an
    input-grad pass of every layer but the first."""
    steps = run.obs.get("steps")
    t = conv_kernel_s(run)
    if not steps or t <= 0:
        return None
    per_chip = run.obs["global_batch"] // len(run.devices)
    one = conv_min_s(run.cfg, per_chip, run.peaks)
    first = conv_min_s(dict(run.cfg, layers=run.cfg["layers"][:1]), per_chip, run.peaks)
    return 100.0 * steps * (3 * one - first) / t


