"""Completed images per second times forward FLOPs per image, over the
chip's bf16 peak: the whole serving path's share of the chip."""

from bench.work import forward_flops


def read(run):
    rate = run.e2e.get("serve_images_per_s")
    if not rate:
        return None
    return 100.0 * rate * forward_flops(run.cfg) / run.peaks["bf16_flops"]
