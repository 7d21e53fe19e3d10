"""The forward, input-grad and weight-grad conv kernels' share of their
roofline over the window's steps (device trace)."""

from bench.roofline import train_conv_share as read  # noqa: F401
