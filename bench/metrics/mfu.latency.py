"""Forward FLOPs of the served images over the server's batch time (from
dispatch to hand-off, the server's own clock) times the chip's bf16
peak: the whole serving step's share of the chip while it works."""

from bench.work import forward_flops


def read(run):
    batch_s = run.obs.get("batch_s")
    if not batch_s:
        return None
    return 100.0 * run.obs["served"] * forward_flops(run.cfg) / (batch_s * run.peaks["bf16_flops"])
