"""TrIM-SSD — the Mamba2 chunked SSD scan as a Pallas TPU kernel.

The §Perf analysis of the mamba2-130m train cell shows the XLA-visible SSD
materializing its within-chunk quadratic tensors ((CS, CS) decay/score
blocks) in HBM ~tens of times per layer — the dominant roofline memory
term. This kernel is the TrIM treatment of that hot spot:

- the inter-chunk state h (P, S) lives in VMEM scratch and is carried
  across the chunk grid axis — the engine's psum-buffer temporal
  accumulation, verbatim;
- the (CS, CS) quadratic block (segsum decays, CB^T scores) exists ONLY in
  VMEM/registers inside one grid step — the single-fetch discipline: HBM
  traffic is x/dt/B/C in once, y out once;
- grid (B, H, NC) with NC innermost so the revolving-buffer pipeline keeps
  the per-(b, h) state resident while chunks stream.

Forward-only (serving / activation recompute; the XLA path remains the
differentiable reference). x (B, L, H, P); dt (B, L, H) post-softplus;
A (H,); Bm/Cm (B, L, G, S) with G == 1 supported in-kernel (groups > 1:
pre-repeat outside). Matches ``ref.ssd_ref`` == ``nn.mamba.ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_ref, *,
                CS: int, n_chunks: int):
    """One grid step: chunk ci of one (batch, head)."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (CS, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (CS, 1)
    a = a_ref[0]                                 # scalar, negative
    Bm = b_ref[0, 0].astype(jnp.float32)         # (CS, S)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (CS, S)
    D = d_ref[0]                                 # scalar

    dA = dt[:, 0] * a                         # (CS,)
    cum = jnp.cumsum(dA)                         # inclusive within-chunk
    # within-chunk quadratic term — VMEM only
    seg = cum[:, None] - cum[None, :]            # (CS, CS)
    tri = jax.lax.broadcasted_iota(jnp.int32, (CS, CS), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (CS, CS), 1)
    Lmat = jnp.where(tri, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = CB * Lmat * dt[:, 0][None, :]       # (CS, CS)
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk contribution from the carried state
    h = h_ref[...]                               # (P, S)
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y + x * D
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h' = exp(sum dA) h + sum_t exp(cum_last - cum_t) dt_t x_t B_t^T
    decay_to_end = jnp.exp(cum[CS - 1] - cum) * dt[:, 0]     # (CS,)
    dBx = jax.lax.dot_general(x * decay_to_end[:, None], Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, S)
    h_ref[...] = jnp.exp(cum[CS - 1]) * h + dBx


def trim_ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, D: jax.Array, *,
                    chunk: int = 256, interpret: bool = False) -> jax.Array:
    """x (B, L, H, P); dt (B, L, H); A (H,); Bm/Cm (B, L, H, S) (pre-repeated
    per head); D (H,) -> y (B, L, H, P)."""
    Bb, L, H, P = x.shape
    S = Bm.shape[-1]
    CS = min(chunk, L)
    NC = -(-L // CS)
    pad = NC * CS - L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # layout: (B, H, NC*CS, feat) so the chunk axis tiles cleanly
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1)[..., None]
    bt = Bm.transpose(0, 2, 1, 3)
    ct = Cm.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, CS=CS, n_chunks=NC)
    out = pl.pallas_call(
        kernel,
        grid=(Bb, H, NC),
        in_specs=[
            pl.BlockSpec((1, 1, CS, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, CS, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, CS, S), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, CS, S), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, 1, CS, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, NC * CS, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, S), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), bt, ct, D.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3)[:, :L]


def ssd_ref(x, dt, A, Bm, Cm, D, chunk: int = 256):
    """Oracle: nn.mamba.ssd_chunked with per-head B/C (G == H)."""
    from repro.nn.mamba import ssd_chunked
    y, _ = ssd_chunked(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32), D,
                       chunk=chunk)
    return y
