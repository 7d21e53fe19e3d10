"""Collective time per training step during which nothing else ran on
the chip (device trace), averaged over the chips: the part of the
gradients' exchange between chips that the step does not hide behind
compute.  Nothing to read where the window ran no collective."""

from bench.trace_reduce import is_collective


def read(run):
    steps = run.obs.get("steps")
    if run.reduced is None or not steps or not run.reduced.count(is_collective):
        return None
    return 1e3 * run.reduced.collective_exposed_s() / steps
