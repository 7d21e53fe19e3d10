"""Backward-pass (VJP) Pallas kernels for the TrIM conv2d (DESIGN.md §6).

The forward kernel realizes the paper's triangular input movement; training
additionally needs dL/dx and dL/dw.  Both gradients are themselves
TrIM-shaped sweeps and reuse the forward machinery:

- **Input grad** — a transposed conv expressed as a TrIM *forward*: the
  stride-folded weights (DESIGN.md §2) are flipped spatially and
  transposed (Kf,Kf,S*S*C,F) -> (Kf,Kf,F,S*S*C), and
  ``trim_conv2d_pallas`` runs at stride 1 over the undilated cotangent —
  same halo-row/halo-column block maps, same ``pick_tile_w`` VMEM sizing,
  zero new kernel code; its S*S*C output channels unfold to pixels.
- **Weight grad** — a per-(K,K)-tap reduction: for every tap,
  ``dw[kh, kw] += <shifted input window, cotangent tile>`` — the (Cb, Fb)
  contraction over the output tile's spatial extent — accumulated in an
  fp32 (K, K, Cb, Fb) VMEM scratch across the batch/row/column grid axes.
  It is the forward kernel with the roles of weights and outputs
  exchanged: the dw block's index_map is constant along the spatial axes
  (stationary, like the forward's weights) and is written exactly once,
  on the last spatial step (the forward's psum pattern).

``make_trim_conv2d_vjp`` packages both under ``jax.custom_vjp`` around the
epilogue-fused forward (bias + ReLU in the flush): the ReLU mask is
*reconstructed* from the saved post-activation output (out > 0 <=>
pre-activation > 0, and relu'(0) = 0 either way), so no pre-activation
psums are stashed; dbias is the masked cotangent summed over N/H/W.
Float path only — the integer/requant datapath stays forward-only, as
does the ``ExecutionPolicy(emulate_hw=True)`` decimation replay (the
planner routes both around the VJP — ``repro.engine.execute``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.trim_conv2d import (VMEM_BUDGET_BYTES,
                                       assemble_halo_tile, conv2d_geom,
                                       fold_conv2d_w, halo_x_specs,
                                       pad_conv2d_x, trim_conv2d_pallas,
                                       unfold_conv2d_w)


def trim_conv2d_input_grad(g_out: jax.Array, w: jax.Array, *,
                           x_hw, stride: int = 1,
                           padding: Optional[int] = None,
                           tile_h: int = 8, tile_w: Optional[int] = None,
                           block_c: int = 128, block_f: int = 128,
                           vmem_budget: int = VMEM_BUDGET_BYTES,
                           out_dtype=None,
                           interpret: bool = False) -> jax.Array:
    """dL/dx of the TrIM conv: g_out (N,H_O,W_O,F), w (K,K,C,F) -> (N,H,W,C).

    The forward runs as a stride-1 conv of the stride-folded input with
    the folded weights (``fold_conv2d_w``, DESIGN.md §2), so its input
    grad is the stride-1 transposed conv of the cotangent with the folded
    weights flipped spatially and transposed to (Kf,Kf,F,S*S*C): the
    *forward* kernel again, with no zero-stuffing.  Its S*S*C output
    channels are the stride phases of dL/d(padded x), unfolded back to
    pixels.  Only the folded rows/cols that hold x itself are computed:
    the cotangent is padded (or cropped) so the valid sweep starts at
    folded row p//S.  Pixels the strided sweep never touched get zero.
    ``block_c``/``block_f`` keep the forward-call meaning (folded C and F
    of the *forward* conv) and are swapped internally.
    """
    N, H_O, W_O, F = g_out.shape
    K, _, C, _ = w.shape
    H, W = x_hw
    S = int(stride)
    p = K // 2 if padding is None else padding
    Kf = -(-K // S)

    def span(n_out, n_x):
        # Folded rows [t0, t1) hold x; row t gathers cotangent rows
        # t-Kf+1 .. t, so the sweep reads cotangent rows [t0-Kf+1, t1).
        t0, t1 = p // S, -(-(p + n_x) // S)
        lo = Kf - 1 - t0
        return t0, lo, (t1 - t0 + Kf - 1) - (n_out + lo)

    t0h, loh, hih = span(H_O, H)
    t0w, low, hiw = span(W_O, W)
    # lax.pad: negative widths crop cotangent rows that only meet padding.
    gp = jax.lax.pad(g_out, jnp.zeros((), g_out.dtype),
                     ((0, 0, 0), (loh, hih, 0), (low, hiw, 0), (0, 0, 0)))
    w_t = fold_conv2d_w(w, S)[::-1, ::-1].transpose(0, 1, 3, 2)
    dxf = trim_conv2d_pallas(gp, w_t, stride=1, padding=0, tile_h=tile_h,
                             tile_w=tile_w, block_c=block_f, block_f=block_c,
                             vmem_budget=vmem_budget, out_dtype=out_dtype,
                             interpret=interpret)
    R, Cc = dxf.shape[1:3]
    dx = dxf.reshape(N, R, Cc, S, S, C).transpose(0, 1, 3, 2, 4, 5)
    dx = dx.reshape(N, R * S, Cc * S, C)
    oh, ow = p - t0h * S, p - t0w * S
    return dx[:, oh:oh + H, ow:ow + W, :]


def _trim_conv2d_wgrad_kernel(*refs, K: int, TH: int, TW: int,
                              n_steps: int, n_wt: int, tiled: bool,
                              has_halo_h: bool, has_halo_w: bool):
    """One grid step: accumulate every (kh, kw) tap's (Cb, Fb) contribution
    from one (TH, TW) output tile into the stationary dw scratch."""
    it = iter(refs)
    x_ll_ref = next(it)
    x_lh_ref = next(it) if has_halo_w else None
    x_hl_ref = next(it) if has_halo_h else None
    x_hh_ref = next(it) if (has_halo_h and has_halo_w) else None
    g_ref = next(it)
    dw_ref = next(it)
    acc_ref = next(it)

    step = (pl.program_id(2) * n_wt + pl.program_id(3) if tiled
            else pl.program_id(2))

    @pl.when(step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = assemble_halo_tile(x_ll_ref, x_lh_ref, x_hl_ref, x_hh_ref, K - 1)
    gt = g_ref[0]                           # (TH, TW, Fb)
    cb = x.shape[-1]
    fb = gt.shape[-1]
    g2 = gt.reshape(TH * TW, fb)
    # The forward's K*K shifted views of the same resident tile, contracted
    # against the cotangent tile instead of the weights.
    for kh in range(K):
        for kw in range(K):
            patch = x[kh:kh + TH, kw:kw + TW]
            tap = jax.lax.dot_general(
                patch.reshape(TH * TW, cb), g2,
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (Cb, Fb)
            acc_ref[kh, kw] = acc_ref[kh, kw] + tap

    @pl.when(step == n_steps - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def trim_conv2d_wgrad_pallas(x: jax.Array, g_out: jax.Array, *, K: int,
                             stride: int = 1,
                             padding: Optional[int] = None,
                             tile_h: int = 8, tile_w: Optional[int] = None,
                             block_c: int = 128, block_f: int = 128,
                             vmem_budget: int = VMEM_BUDGET_BYTES,
                             out_dtype=None,
                             interpret: bool = False) -> jax.Array:
    """dL/dw of the TrIM conv: x (N,H,W,C), g_out (N,H_O,W_O,F) ->
    (K,K,C,F).

    Reuses the forward geometry verbatim (``conv2d_geom`` — same TH/TW
    tiles, same haloed ll/lh/hl/hh input block maps over the same
    stride-folded input); the grid is reordered to
    ``(n_ci, n_f, N*n_ht[, n_wt])`` so the spatial/batch reduction axes
    are innermost and the (Kf,Kf,Cb,Fb) fp32 scratch integrates across
    them, written back once on the last step and unfolded to (K,K,C,F).
    """
    N, H, W, C = x.shape
    _, H_O, W_O, F = g_out.shape
    geo = conv2d_geom(x.shape, (K, K, C, F), stride=stride, padding=padding,
                      tile_h=tile_h, tile_w=tile_w, block_c=block_c,
                      block_f=block_f, in_sz=x.dtype.itemsize,
                      w_sz=g_out.dtype.itemsize,
                      out_sz=jnp.dtype(x.dtype).itemsize,
                      vmem_budget=vmem_budget)
    assert (H_O, W_O) == (geo.H_O, geo.W_O), ((H_O, W_O), geo)
    if out_dtype is None:
        out_dtype = x.dtype
    TH, TW, n_ht, n_wt = geo.TH, geo.TW, geo.n_ht, geo.n_wt
    Cb, n_ci, Fb, n_f = geo.Cb, geo.n_ci, geo.Fb, geo.n_f

    x_pad = pad_conv2d_x(x, geo)
    # Cotangent padded to the output grid extent — the zero rows/cols/
    # channels contribute nothing to the dw sums.
    g_pad = jnp.pad(g_out, ((0, 0), (0, n_ht * TH - H_O),
                            (0, n_wt * TW - W_O), (0, n_f * Fb - F)))

    NB = N * n_ht
    if geo.tiled:
        grid = (n_ci, n_f, NB, n_wt)

        def x_idx(dh, dw):
            return lambda c, f, bt, wt: (bt // n_ht, bt % n_ht + dh,
                                         wt + dw, c)

        def g_idx(c, f, bt, wt):
            return (bt // n_ht, bt % n_ht, wt, f)

        def o_idx(c, f, bt, wt):
            return (0, 0, c, f)
    else:
        grid = (n_ci, n_f, NB)

        def x_idx(dh, dw):
            return lambda c, f, bt: (bt // n_ht, bt % n_ht + dh, 0, c)

        def g_idx(c, f, bt):
            return (bt // n_ht, bt % n_ht, 0, f)

        def o_idx(c, f, bt):
            return (0, 0, c, f)

    inputs, in_specs = halo_x_specs(x_pad, geo, x_idx)
    inputs.append(g_pad)
    in_specs.append(pl.BlockSpec((1, TH, TW, Fb), g_idx))

    Kf = geo.Kf
    kernel = functools.partial(
        _trim_conv2d_wgrad_kernel, K=Kf, TH=TH, TW=TW,
        n_steps=NB * n_wt, n_wt=n_wt, tiled=geo.tiled,
        has_halo_h=geo.has_halo, has_halo_w=geo.has_halo and geo.tiled)
    dw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Kf, Kf, Cb, Fb), o_idx),
        out_shape=jax.ShapeDtypeStruct((Kf, Kf, n_ci * Cb, n_f * Fb),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((Kf, Kf, Cb, Fb), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return unfold_conv2d_w(dw[:, :, :geo.S ** 2 * C, :F], geo.S, K)


@functools.lru_cache(maxsize=None)
def make_trim_conv2d_vjp(*, stride: int, padding: Optional[int], relu: bool,
                         has_bias: bool, tile_h: int, tile_w: Optional[int],
                         block_c: int, block_f: int, interpret: bool,
                         vmem_budget: int = VMEM_BUDGET_BYTES):
    """Build the ``jax.custom_vjp``-wrapped fused TrIM conv for one static
    configuration (cached so repeated traces reuse one primitive).

    Returns ``f(x, w, bias)`` when ``has_bias`` else ``f(x, w)``; the
    forward is the epilogue-fused Pallas kernel, the backward the
    input-grad/weight-grad Pallas pair above.  Cotangent dtypes follow the
    primals (dx: x.dtype, dw: w.dtype, dbias: bias.dtype).
    """
    kw = dict(stride=stride, padding=padding, tile_h=tile_h, tile_w=tile_w,
              block_c=block_c, block_f=block_f, vmem_budget=vmem_budget,
              interpret=interpret)

    def fwd_call(x, w, bias):
        return trim_conv2d_pallas(x, w, bias=bias, relu=relu, **kw)

    def bwd_core(x, w, out, g):
        if relu:
            # out = relu(pre): the mask is recoverable from the saved
            # activation — no pre-activation stash (DESIGN.md §6).
            g = g * (out > 0).astype(g.dtype)
        dx = trim_conv2d_input_grad(g, w, x_hw=x.shape[1:3],
                                    out_dtype=x.dtype, **kw)
        dw = trim_conv2d_wgrad_pallas(x, g, K=w.shape[0],
                                      out_dtype=w.dtype, **kw)
        return dx, dw, g

    if has_bias:
        @jax.custom_vjp
        def conv(x, w, b):
            return fwd_call(x, w, b)

        def conv_fwd(x, w, b):
            out = fwd_call(x, w, b)
            return out, (x, w, b, out)

        def conv_bwd(res, g):
            x, w, b, out = res
            dx, dw, gm = bwd_core(x, w, out, g)
            db = gm.astype(jnp.float32).sum(axis=(0, 1, 2)).astype(b.dtype)
            return dx, dw, db

        conv.defvjp(conv_fwd, conv_bwd)
        return conv

    @jax.custom_vjp
    def conv_nb(x, w):
        return fwd_call(x, w, None)

    def conv_nb_fwd(x, w):
        out = fwd_call(x, w, None)
        return out, (x, w, out)

    def conv_nb_bwd(res, g):
        x, w, out = res
        dx, dw, _ = bwd_core(x, w, out, g)
        return dx, dw

    conv_nb.defvjp(conv_nb_fwd, conv_nb_bwd)
    return conv_nb
