"""The conv kernels' share of their roofline over the window (device
trace): the least time the window's batches need, at the bf16 peak and
the HBM bandwidth, over the kernels' measured time."""

from bench.roofline import serve_conv_share as read  # noqa: F401
