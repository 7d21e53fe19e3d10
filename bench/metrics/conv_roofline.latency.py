"""The conv kernels' share of their roofline over the window's batches
(device trace), every flushed bucket counted whole."""

from bench.roofline import serve_conv_share as read  # noqa: F401
