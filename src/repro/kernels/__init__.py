"""Pallas TPU kernels for the paper's compute hot-spots + the §Perf
attention kernel, each with a jit'd wrapper (ops.py) and a pure-jnp oracle
(ref.py) asserted against in tests:

- trim_conv2d — the paper's TrIM dataflow on the TPU memory hierarchy
  (single-fetch haloed input tiles, weight-stationary, VMEM psum accum),
  stride-aware with a fused bias/ReLU/requant epilogue (DESIGN.md §2) and
  a custom VJP (trim_conv2d_vjp — transposed-conv input-grad + per-tap
  weight-grad Pallas kernels, DESIGN.md §6) so training runs TrIM in both
  directions.
- trim_conv1d — TrIM-1D causal depthwise conv (the Mamba short-conv).
- trim_matmul — the K=1 degenerate TrIM (weight-stationary blocked GEMM).
- flash_attention — fused streaming-softmax attention (scores in VMEM),
  the answer to the dominant roofline memory term (§Perf).
- trim_ssd — the Mamba2 chunked SSD scan with the (CS, CS) quadratic block
  VMEM-resident and the inter-chunk state carried in scratch (the TrIM
  psum-buffer pattern; the mamba2 train cell's deep §Perf fix).
"""
from repro.kernels.trim_conv2d_vjp import (  # noqa: F401
    trim_conv2d_input_grad, trim_conv2d_wgrad_pallas)
from repro.kernels.flash_attention import (  # noqa: F401
    flash_attention_pallas, flash_attention_ref)
from repro.kernels.trim_ssd import trim_ssd_pallas  # noqa: F401

#: ops re-exports resolve lazily (PEP 562): ops.py sits *above* the engine
#: (it shims legacy kwargs onto repro.engine plans), and repro.engine
#: imports the kernel modules from this package — an eager import here
#: would close that cycle during package init.
_OPS_EXPORTS = ("trim_conv1d", "trim_conv2d", "trim_matmul")


def __getattr__(name):
    if name in _OPS_EXPORTS:
        from repro.kernels import ops

        return getattr(ops, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
