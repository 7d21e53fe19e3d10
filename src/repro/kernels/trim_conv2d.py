"""TrIM conv2d — the paper's dataflow, realized as a Pallas TPU kernel.

Mapping of the paper's triangular input movement onto the TPU memory
hierarchy (DESIGN.md §2, §4):

- **Single-fetch inputs**: each haloed input tile travels HBM -> VMEM
  exactly once per (spatial, C_in) grid step and is then reused K*K times
  via *shifted VMEM slices* — the horizontal + diagonal movements of the
  paper collapse into VMEM addressing (the halo rows/columns play the role
  of the shift-register buffers).
- **Weight-stationary**: the (K, K, Cb, Fb) weight block's index_map is
  constant along the spatial grid axes, so Pallas' revolving-buffer pipeline
  keeps it resident in VMEM while the spatial sweep runs (the paper's
  weights loaded once, held for the whole layer).
- **Psum accumulation**: a VMEM scratch accumulator integrates over the
  C_in grid axis (the engine's ceil(M/P_M) temporal steps + psum buffers);
  the output tile is written exactly once, on the last C_in step (the
  paper's single quantized writeback).
- **Stride-aware sweep**: a stride-S conv is folded into a stride-1 conv
  in the wrapper (space-to-depth, ``pad_conv2d_x`` / ``pad_conv2d_w``):
  the padded input's S*S stride phases become channels, (N, H, W, C) ->
  (N, H/S, W/S, S*S*C), and the weights fold the same way, (K, K, C, F)
  -> (Kf, Kf, S*S*C, F) with Kf = ceil(K/S) (taps past K are zero).  The
  kernel then only ever takes unit-stride slices (Mosaic accepts no
  strided value slice) and computes only the H_O x W_O strided outputs;
  AlexNet CL1 (K=11, S=4) becomes 3x3 taps over 48 channels instead of
  121 taps over 3.  The FPGA instead streams
  the full stride-1 extent and decimates downstream (§V, AlexNet CL1); that
  behaviour is preserved for honest Table I/II comparisons — request it
  with ``ExecutionPolicy(emulate_hw=True)`` and plan through
  ``repro.engine`` (``plan_conv_layer`` / ``plan_model``; DESIGN.md §3).
- **Width tiling** (DESIGN.md §4): W_O is split into ``n_wt`` tiles of TW
  output columns; each input block is a ``(TH, TW + Kf - 1)`` window
  (folded pixels) with Kf-1 halo columns, mirroring the halo-row logic, so
  maps wider than
  the VGG/AlexNet shapes no longer blow VMEM.  ``tile_w=None`` auto-picks
  TW from a VMEM budget (``pick_tile_w``); ``n_wt == 1`` degenerates to
  the original single-block layout (same grid, same schedule).
- **Fused epilogue**: bias add + ReLU + requantization (power-of-two shift
  or arbitrary-scale multiplier+shift, ``kernels/requant.py``) run in the
  final-C_in flush, so the int32 psums never round-trip through HBM
  between conv, bias, activation, and quant.
- **Engine broadcast**: the input tile's index_map does not depend on the
  F (C_out) grid axis — the same fetched inputs serve all P_N "cores".

Halos are expressed with plain blocked BlockSpecs by passing the input
multiple times at shifted block indices — row-block ht+1 for the Kf-1
halo rows, column-block wt+1 for the Kf-1 halo columns (up to four passes
when width-tiled) — and concatenating inside the kernel.  This keeps the
kernel compatible with both compiled TPU lowering and interpret=True CPU
validation.  When K <= S no halo is needed and the input is passed once.

Supports float (bf16/f32 in, f32 accum) and the paper's integer mode
(uint8 x int8 -> int32 accum).  The chip's matrix unit takes no int32
operands, so each integer tap is an exact bf16 x bf16 -> f32 dot: 8-bit
values are exact in bf16, each product is at most 255*128, and one tap
contracts over Cb channels, so the f32 partial sums stay exact integers
below 2**24 while Cb <= ``INT_EXACT_MAX_CB`` (514).  Each tap's f32 result
then converts to int32 and adds into the int32 accumulator.

The tiling geometry (``conv2d_geom``), padding (``pad_conv2d_x`` /
``pad_conv2d_w``), halo BlockSpec construction (``halo_x_specs``) and
in-kernel halo assembly (``assemble_halo_tile``) are shared with the
backward pass (``trim_conv2d_vjp.py``, DESIGN.md §6): the weight-grad
kernel sweeps the *same* haloed input blocks and the input-grad kernel is
this forward kernel applied to the cotangent with the flipped folded
weights.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.requant import requant_mult_shift

#: Largest channel block whose per-tap bf16 dot stays exact for uint8 x int8
#: operands: Cb * 255 * 128 <= 2**24 (the bound ``ref.conv2d_exact_f32``
#: chunks by).
INT_EXACT_MAX_CB = (1 << 24) // (255 * 128)

#: Default per-core VMEM budget for the width-tile auto-pick: conservative
#: vs the ~16 MiB of a TPU core so weights + revolving buffers still fit.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def _acc_dtype(x_dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(x_dtype, jnp.integer) else jnp.float32


def _vmem_bytes(*, cols: int, Cb: int, Fb: int, K: int, TH: int, TW: int,
                passes: int, in_sz: int, w_sz: int, out_sz: int) -> int:
    """Estimated VMEM for one grid step: double-buffered in/out blocks
    (``passes`` input blocks of TH x ``cols`` folded pixels) + the weight
    block + the psum scratch."""
    xb = passes * TH * cols * Cb * in_sz
    wb = K * K * Cb * Fb * w_sz
    ob = TH * TW * Fb * out_sz
    ab = TH * TW * Fb * 4
    return 2 * (xb + wb + ob) + ab


def pick_tile_w(W_O: int, *, K: int, TH: int, Cb: int, Fb: int,
                in_sz: int = 4, w_sz: int = 4, out_sz: int = 4,
                vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """Auto-pick the output-column tile TW from a VMEM budget.

    Sizes the stride-folded stride-1 problem the kernels run (module
    docstring): ``K`` taps per axis (``Kf``), ``Cb`` folded channels.
    Returns ``W_O`` (single block — the degenerate layout) whenever the
    full-width block fits the budget, so the VGG/AlexNet shapes keep their
    original schedule; otherwise halves TW (rounded up to a multiple of 8
    sublanes) until the 4-pass haloed tile fits.
    """
    halo = K - 1
    full = _vmem_bytes(cols=W_O + halo, Cb=Cb, Fb=Fb, K=K, TH=TH,
                       TW=W_O, passes=2 if halo else 1, in_sz=in_sz,
                       w_sz=w_sz, out_sz=out_sz)
    if full <= vmem_budget:
        return W_O
    TW = W_O
    while TW > 8:
        TW = -(-TW // 2)
        TW = -(-TW // 8) * 8
        used = _vmem_bytes(cols=TW, Cb=Cb, Fb=Fb, K=K, TH=TH, TW=TW,
                           passes=4 if halo else 1, in_sz=in_sz, w_sz=w_sz,
                           out_sz=out_sz)
        if used <= vmem_budget:
            break
    return min(max(TW, halo), W_O)


@dataclasses.dataclass(frozen=True)
class Conv2DGeom:
    """Tiling geometry shared by the forward and weight-grad kernels.

    Both passes sweep identical haloed input blocks with identical
    (TH, TW) output tiles (DESIGN.md §2, §4, §6); computing the geometry
    once keeps their block maps bit-identical.  Everything past ``W_O``
    describes the stride-folded stride-1 problem the kernels run
    (module docstring): rows/cols count folded pixels (S x S input
    pixels each), channels count folded channels (S*S*C).
    """
    S: int                  # stride of the conv as called
    p: int                  # symmetric spatial padding
    Kf: int                 # folded kernel extent ceil(K/S): taps per axis
    H_O: int
    W_O: int
    halo: int               # Kf - 1 halo rows/cols (when > 0)
    has_halo: bool
    TH: int                 # output rows per tile
    n_ht: int
    TW: int                 # output cols per tile
    n_wt: int
    tiled: bool             # n_wt > 1 (width-tiled grid)
    CB: int                 # folded cols per spatial block
    Cb: int                 # folded channels per C_in block
    n_ci: int
    Fb: int
    n_f: int
    rows_needed: int        # folded rows (block multiples + halo)
    cols_needed: int


def conv2d_geom(x_shape, w_shape, *, stride: int, padding: Optional[int],
                tile_h: int, tile_w: Optional[int], block_c: int,
                block_f: int, in_sz: int = 4, w_sz: int = 4,
                out_sz: int = 4,
                vmem_budget: int = VMEM_BUDGET_BYTES) -> Conv2DGeom:
    """Derive the blocked-grid geometry for x (N,H,W,C), w (K,K,C,F)."""
    N, H, W, C = x_shape
    K, K2, Cw, F = w_shape
    assert K == K2 and Cw == C, (x_shape, w_shape)
    S = int(stride)
    assert S >= 1
    p = K // 2 if padding is None else padding
    H_p, W_p = H + 2 * p, W + 2 * p
    assert H_p >= K and W_p >= K, (x_shape, w_shape, p)
    H_O, W_O = (H_p - K) // S + 1, (W_p - K) // S + 1

    # The stride-S conv runs as a stride-1 conv over the S x S folded
    # input with Kf x Kf folded taps (module docstring).
    Kf = -(-K // S)
    halo = Kf - 1
    has_halo = halo > 0
    TH = min(tile_h, H_O)
    if has_halo:
        # The halo comes from a single following row block, so the block
        # must be tall enough to contain it: Kf-1 <= TH.  (Covers large
        # kernels at stride 1 — e.g. K=11 — and tiny maps.)
        TH = max(TH, halo)
    n_ht = -(-H_O // TH)                    # ceil
    Cf = S * S * C
    Cb = min(block_c, Cf)
    n_ci = -(-Cf // Cb)
    Fb = min(block_f, F)
    n_f = -(-F // Fb)

    if tile_w is not None:
        TW = min(int(tile_w), W_O)
    else:
        TW = pick_tile_w(W_O, K=Kf, TH=TH, Cb=Cb, Fb=Fb, in_sz=in_sz,
                         w_sz=w_sz, out_sz=out_sz, vmem_budget=vmem_budget)
    if has_halo:
        # Same single-following-block constraint along the width.
        TW = max(TW, halo)
    n_wt = -(-W_O // TW)                    # ceil
    tiled = n_wt > 1
    if not tiled:
        TW = W_O

    # Row padding: n_ht blocks of TH folded rows cover the sweep; one
    # extra block (halo case) makes the ht+1 halo index always valid.
    rows_needed = (n_ht + (1 if has_halo else 0)) * TH
    if tiled:
        # Column padding mirrors the rows: n_wt blocks of TW folded
        # columns plus one extra block backing the wt+1 halo columns.
        CB = TW
        cols_needed = (n_wt + (1 if has_halo else 0)) * TW
    else:
        CB = cols_needed = W_O + halo
    return Conv2DGeom(S=S, p=p, Kf=Kf, H_O=H_O, W_O=W_O, halo=halo,
                      has_halo=has_halo, TH=TH, n_ht=n_ht, TW=TW, n_wt=n_wt,
                      tiled=tiled, CB=CB, Cb=Cb, n_ci=n_ci, Fb=Fb,
                      n_f=n_f, rows_needed=rows_needed,
                      cols_needed=cols_needed)


def pad_conv2d_x(x: jax.Array, g: Conv2DGeom) -> jax.Array:
    """Zero-pad x (N,H,W,C) by the p-border, crop or pad it to the blocked
    grid extent (block-multiple rows/cols/channels — free w.r.t. the conv
    result), and fold the stride phases into channels:
    ``out[n, i, j, (a*S + b)*C + c] = padded[n, i*S + a, j*S + b, c]``."""
    N, H, W, C = x.shape
    S, R, Cc = g.S, g.rows_needed, g.cols_needed
    xp = jnp.pad(x, ((0, 0), (g.p, max(R * S - H - g.p, 0)),
                     (g.p, max(Cc * S - W - g.p, 0)), (0, 0)))
    # Rows/cols past the last output's receptive field never reach a
    # nonzero tap: cropping them keeps the fold a plain reshape.
    xp = xp[:, :R * S, :Cc * S]
    if S > 1:
        xp = xp.reshape(N, R, S, Cc, S, C).transpose(0, 1, 3, 2, 4, 5)
        xp = xp.reshape(N, R, Cc, S * S * C)
    return jnp.pad(xp, ((0, 0), (0, 0), (0, 0),
                        (0, g.n_ci * g.Cb - xp.shape[-1])))


def fold_conv2d_w(w: jax.Array, S: int) -> jax.Array:
    """Fold w (K,K,C,F) to (Kf,Kf,S*S*C,F): ``out[i, j, (a*S + b)*C + c]
    = w[i*S + a, j*S + b, c]``, zero past K."""
    K, _, C, F = w.shape
    if S == 1:
        return w
    Kf = -(-K // S)
    e = Kf * S - K
    w = jnp.pad(w, ((0, e), (0, e), (0, 0), (0, 0)))
    w = w.reshape(Kf, S, Kf, S, C, F).transpose(0, 2, 1, 3, 4, 5)
    return w.reshape(Kf, Kf, S * S * C, F)


def unfold_conv2d_w(wf: jax.Array, S: int, K: int) -> jax.Array:
    """Inverse of ``fold_conv2d_w``: (Kf,Kf,S*S*C,F) -> (K,K,C,F)."""
    if S == 1:
        return wf
    Kf, _, SSC, F = wf.shape
    C = SSC // (S * S)
    wf = wf.reshape(Kf, Kf, S, S, C, F).transpose(0, 2, 1, 3, 4, 5)
    return wf.reshape(Kf * S, Kf * S, C, F)[:K, :K]


def pad_conv2d_w(w: jax.Array, g: Conv2DGeom) -> jax.Array:
    """Fold w's stride phases into channels (``fold_conv2d_w``) and
    zero-pad the folded channels/filters to block multiples."""
    w = fold_conv2d_w(w, g.S)
    return jnp.pad(w, ((0, 0), (0, 0), (0, g.n_ci * g.Cb - w.shape[2]),
                       (0, g.n_f * g.Fb - w.shape[3])))


def halo_x_specs(x_pad: jax.Array, g: Conv2DGeom,
                 x_idx: Callable[[int, int], Callable]):
    """The up-to-four shifted passes of the padded input (the ll/lh/hl/hh
    table of DESIGN.md §4).  ``x_idx(dh, dw)`` must return the index_map
    for a pass shifted ``dh`` row blocks and ``dw`` column blocks; the
    grid signature is the caller's (forward and weight-grad kernels order
    their grids differently)."""
    xspec = (1, g.TH, g.CB, g.Cb)
    inputs = [x_pad]
    specs = [pl.BlockSpec(xspec, x_idx(0, 0))]
    if g.has_halo and g.tiled:              # lh: halo columns, top rows
        inputs.append(x_pad)
        specs.append(pl.BlockSpec(xspec, x_idx(0, 1)))
    if g.has_halo:                          # hl: halo rows
        inputs.append(x_pad)
        specs.append(pl.BlockSpec(xspec, x_idx(1, 0)))
    if g.has_halo and g.tiled:              # hh: halo corner
        inputs.append(x_pad)
        specs.append(pl.BlockSpec(xspec, x_idx(1, 1)))
    return inputs, specs


def assemble_halo_tile(x_ll_ref, x_lh_ref, x_hl_ref, x_hh_ref,
                       halo: int) -> jax.Array:
    """Concatenate the ll/lh/hl/hh passes into the haloed VMEM tile —
    (TH + halo, cols + halo, Cb) folded pixels, each fetched exactly once
    per grid step (shared by forward and weight-grad)."""
    x = x_ll_ref[0]                         # (TH, cols, Cb)
    if x_lh_ref is not None:
        x = jnp.concatenate([x, x_lh_ref[0][:, :halo]], axis=1)
    if x_hl_ref is not None:
        bot = x_hl_ref[0][:halo]
        if x_hh_ref is not None:
            bot = jnp.concatenate([bot, x_hh_ref[0][:halo, :halo]], axis=1)
        x = jnp.concatenate([x, bot], axis=0)
    return x


def _trim_conv2d_kernel(*refs, K: int, TH: int, TW: int, n_cin: int,
                        ci_axis: int, has_halo_h: bool,
                        has_halo_w: bool, has_bias: bool, relu: bool,
                        requant_shift: Optional[int], has_requant: bool):
    """One grid step: TH output rows x TW cols x Fb filters, one Cin block
    (``K`` is the folded tap count Kf)."""
    it = iter(refs)
    x_ll_ref = next(it)
    x_lh_ref = next(it) if has_halo_w else None
    x_hl_ref = next(it) if has_halo_h else None
    x_hh_ref = next(it) if (has_halo_h and has_halo_w) else None
    w_ref = next(it)
    b_ref = next(it) if has_bias else None
    m_ref = next(it) if has_requant else None
    s_ref = next(it) if has_requant else None
    o_ref = next(it)
    acc_ref = next(it)

    ci = pl.program_id(ci_axis)

    @pl.when(ci == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Assemble the haloed tile — each input pixel fetched exactly once per
    # (spatial, Cin) step.
    x = assemble_halo_tile(x_ll_ref, x_lh_ref, x_hl_ref, x_hh_ref, K - 1)
    w = w_ref[...]                          # (K, K, Cb, Fb) — stationary
    acc = acc_ref[...]
    integer = acc.dtype == jnp.int32
    if integer:
        # Exact bf16 operands (module docstring): 8-bit -> int32 -> f32 ->
        # bf16; the matrix unit takes no int32 operands.  The tile stays
        # f32 so the shifted slices below are unpacked 32-bit slices.
        x = x.astype(jnp.int32).astype(jnp.float32)
        w = w.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    cb = x.shape[-1]
    fb = w.shape[-1]
    # Triangular reuse: K*K unit-stride views of the SAME resident tile.
    for kh in range(K):
        for kw in range(K):
            patch = x[kh:kh + TH, kw:kw + TW].reshape(TH * TW, cb)
            if integer:
                # Pinned: a caller's default_matmul_precision("highest")
                # makes Mosaic refuse the bf16 operands (exact as they are).
                tap = jnp.dot(patch.astype(jnp.bfloat16), w[kh, kw],
                              precision=jax.lax.Precision.DEFAULT,
                              preferred_element_type=jnp.float32)
                tap = tap.astype(jnp.int32)
            else:
                tap = jnp.dot(patch, w[kh, kw],
                              preferred_element_type=jnp.float32)
            acc = acc + tap.reshape(TH, TW, fb)
    acc_ref[...] = acc

    @pl.when(ci == n_cin - 1)
    def _flush():
        r = acc_ref[...]
        # Fused epilogue: bias -> ReLU -> requant, all while the int32/f32
        # psums are still accumulator-resident.
        if has_bias:
            r = r + b_ref[0]
        if relu:
            r = jnp.maximum(r, 0)
        if requant_shift is not None:
            r = jnp.clip(jnp.right_shift(r, requant_shift), 0, 255)
        if has_requant:
            r = requant_mult_shift(r, m_ref[0], s_ref[0])
        o_ref[0] = r.astype(o_ref.dtype)


def trim_conv2d_pallas(x: jax.Array, w: jax.Array, *,
                       stride: int = 1,
                       tile_h: int = 8, tile_w: Optional[int] = None,
                       block_c: int = 128,
                       block_f: int = 128, padding: Optional[int] = None,
                       bias: Optional[jax.Array] = None,
                       relu: bool = False,
                       requant_shift: Optional[int] = None,
                       requant: Optional[Tuple[jax.Array, jax.Array]] = None,
                       vmem_budget: int = VMEM_BUDGET_BYTES,
                       out_dtype=None, interpret: bool = False) -> jax.Array:
    """TrIM conv. x (N,H,W,C), w (K,K,C,F) -> (N,H_O,W_O,F).

    ``stride`` is static; only the strided H_O x W_O outputs are computed
    (see DESIGN.md §2).  ``tile_w`` tiles the output width (None: auto-pick
    from ``vmem_budget``; the single-block layout is kept whenever one tile
    covers W_O).  ``bias`` (F,), ``relu``, ``requant_shift`` and ``requant``
    fuse the layer epilogue into the final C_in flush; ``requant_shift``
    (int path only) applies the engine's power-of-two requantization,
    ``requant=(mult, shift)`` (scalars or per-channel (F,) int32 arrays,
    see ``kernels/requant.py``) the arbitrary-scale fixed-point
    requantization — both return uint8.  The wrapper pads H/W/C/F up to
    tile multiples (zero padding is free w.r.t. the convolution result)
    and slices the result back.
    """
    N, H, W, C = x.shape
    K, _, _, F = w.shape
    acc_dtype = _acc_dtype(x.dtype)
    assert requant_shift is None or requant is None, \
        "requant_shift (power-of-two) and requant (mult+shift) are exclusive"
    if requant_shift is not None or requant is not None:
        assert acc_dtype == jnp.int32, "requantization needs the integer path"
        out_dtype = jnp.uint8
    if out_dtype is None:
        out_dtype = acc_dtype if acc_dtype == jnp.int32 else x.dtype

    g = conv2d_geom(x.shape, w.shape, stride=stride, padding=padding,
                    tile_h=tile_h, tile_w=tile_w, block_c=block_c,
                    block_f=block_f, in_sz=x.dtype.itemsize,
                    w_sz=w.dtype.itemsize,
                    out_sz=jnp.dtype(out_dtype).itemsize,
                    vmem_budget=vmem_budget)
    TH, TW, n_ht, n_wt = g.TH, g.TW, g.n_ht, g.n_wt
    Cb, n_ci, Fb, n_f = g.Cb, g.n_ci, g.Fb, g.n_f

    if acc_dtype == jnp.int32:
        assert x.dtype.itemsize == 1 and w.dtype.itemsize == 1, \
            "the integer path takes 8-bit operands"
        assert Cb <= INT_EXACT_MAX_CB, \
            f"block_c {Cb} > {INT_EXACT_MAX_CB}: the bf16 tap dot is inexact"

    x_pad = pad_conv2d_x(x, g)
    w_pad = pad_conv2d_w(w, g)
    Kf = g.Kf

    if g.tiled:
        grid = (N * n_ht, n_wt, n_f, n_ci)
        ci_axis = 3

        def x_idx(dh, dw):
            return lambda bt, wt, f, c: (bt // n_ht, bt % n_ht + dh,
                                         wt + dw, c)

        def chan_idx():
            return lambda bt, wt, f, c: (0, f)

        def w_idx(bt, wt, f, c):
            return (0, 0, c, f)

        def o_idx(bt, wt, f, c):
            return (bt // n_ht, bt % n_ht, wt, f)
    else:
        grid = (N * n_ht, n_f, n_ci)
        ci_axis = 2

        def x_idx(dh, dw):
            return lambda bt, f, c: (bt // n_ht, bt % n_ht + dh, 0, c)

        def chan_idx():
            return lambda bt, f, c: (0, f)

        def w_idx(bt, f, c):
            return (0, 0, c, f)

        def o_idx(bt, f, c):
            return (bt // n_ht, bt % n_ht, 0, f)

    inputs, in_specs = halo_x_specs(x_pad, g, x_idx)
    inputs.append(w_pad)
    in_specs.append(pl.BlockSpec((Kf, Kf, Cb, Fb), w_idx))
    if bias is not None:
        assert bias.shape == (F,), bias.shape
        b_pad = jnp.pad(bias.astype(acc_dtype),
                        (0, n_f * Fb - F)).reshape(1, n_f * Fb)
        inputs.append(b_pad)
        in_specs.append(pl.BlockSpec((1, Fb), chan_idx()))
    if requant is not None:
        mult, shift = requant
        # Scalars broadcast; padded channels carry (m=1, s=15) and their
        # zero psums requantize to 0.
        m_pad = jnp.pad(jnp.broadcast_to(
            jnp.asarray(mult, jnp.int32), (F,)), (0, n_f * Fb - F),
            constant_values=1).reshape(1, n_f * Fb)
        s_pad = jnp.pad(jnp.broadcast_to(
            jnp.asarray(shift, jnp.int32), (F,)), (0, n_f * Fb - F),
            constant_values=15).reshape(1, n_f * Fb)
        inputs.append(m_pad)
        in_specs.append(pl.BlockSpec((1, Fb), chan_idx()))
        inputs.append(s_pad)
        in_specs.append(pl.BlockSpec((1, Fb), chan_idx()))

    kernel = functools.partial(_trim_conv2d_kernel, K=Kf, TH=TH, TW=TW,
                               n_cin=n_ci, ci_axis=ci_axis,
                               has_halo_h=g.has_halo,
                               has_halo_w=g.has_halo and g.tiled,
                               has_bias=bias is not None, relu=relu,
                               requant_shift=requant_shift,
                               has_requant=requant is not None)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TH, TW, Fb), o_idx),
        out_shape=jax.ShapeDtypeStruct((N, n_ht * TH, n_wt * TW, n_f * Fb),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((TH, TW, Fb), acc_dtype)],
        interpret=interpret,
    )(*inputs)
    return out[:, :g.H_O, :g.W_O, :F]
