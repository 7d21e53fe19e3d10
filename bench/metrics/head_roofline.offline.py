"""The serving head's share of its roofline over the window (device
trace): its weights read once per batch at the HBM bandwidth, against
the time of the ``lax.map`` loop that runs it once per image."""

from bench.roofline import serve_head_share as read  # noqa: F401
