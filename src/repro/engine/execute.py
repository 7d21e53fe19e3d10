"""Execute planned TrIM conv layers and planned CNN models.

This module owns the ONLY kernel dispatch site in the tree:
:func:`run_conv2d` takes a resolved :class:`~repro.engine.plan.ConvLayerPlan`
(a ``jax.jit`` static argument) and runs exactly the substrate the plan
chose — the jnp oracle, the compiled Pallas kernel, or Pallas interpret
mode — with the fused epilogue, grouped-conv splitting, the float custom
VJP, and the ``emulate_hw`` decimation replay all handled here once.

The model-level entry points (:func:`forward`, :func:`loss`,
:func:`forward_int8`, :func:`forward_int5`,
:func:`calibrate_requant_shifts`, :func:`calibrate_requant`,
:func:`calibrate_requant_int5`) iterate a :class:`~repro.engine.plan.ModelPlan`'s
per-layer plans; they are what ``ConvNet``, the launchers, and the
benchmarks call — nothing above this layer re-derives kernel kwargs.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.plan import ConvLayerPlan, ModelPlan
from repro.kernels import ref
from repro.kernels.requant import requant_mult_shift
from repro.kernels.trim_conv2d import trim_conv2d_pallas


def apply_epilogue(
    out: jax.Array,
    bias: Optional[jax.Array],
    relu: bool,
    requant_shift: Optional[int],
    requant: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> jax.Array:
    """Unfused epilogue (oracle + emulate_hw decimation arms).

    Bit-identical to the fused kernel flush: the power-of-two path shifts
    without rounding (the engine's output stage) and the multiplier+shift
    path reuses ``kernels.requant.requant_mult_shift``.
    """
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if relu:
        out = jnp.maximum(out, 0)
    if requant_shift is not None:
        out = jnp.clip(jnp.right_shift(out, requant_shift), 0, 255)
        out = out.astype(jnp.uint8)
    if requant is not None:
        out = requant_mult_shift(out, requant[0], requant[1])
        out = out.astype(jnp.uint8)
    return out


def max_pool2x2(x: jax.Array) -> jax.Array:
    """2x2/stride-2 max pool via reshape+max (VALID).  Equivalent to
    reduce_window but robustly reverse-differentiable under nested jit."""
    B, H, W, C = x.shape
    x = x[:, : H // 2 * 2, : W // 2 * 2]
    x = x.reshape(B, H // 2, 2, W // 2, 2, C)
    return x.max(axis=(2, 4))


def _group_call(plan, xg, wg, bg, rq, requant_shift):
    """One conv group on the planned Pallas/interpret substrate."""
    kw = dict(
        padding=plan.padding,
        tile_h=plan.tile_h,
        tile_w=plan.tile_w_arg,
        block_c=plan.block_c,
        block_f=plan.block_f,
        vmem_budget=plan.vmem_budget,
        interpret=plan.interpret,
    )
    if plan.decimate:
        # emulate_hw stays forward-only on the Pallas path (DESIGN.md §6):
        # the FPGA-faithful decimation schedule is an inference/benchmark
        # artifact, not a training datapath.
        s = plan.stride
        o = trim_conv2d_pallas(xg, wg, **kw)
        return o[:, ::s, ::s, :]
    if jnp.issubdtype(xg.dtype, jnp.floating):
        # Float path: the custom-VJP-wrapped fused kernel, so jax.grad
        # runs the Pallas input-grad/weight-grad pair (DESIGN.md §6).
        f = plan.vjp(has_bias=bg is not None)
        return f(xg, wg, bg) if bg is not None else f(xg, wg)
    return trim_conv2d_pallas(
        xg,
        wg,
        stride=plan.stride,
        bias=bg,
        relu=plan.relu,
        requant_shift=requant_shift,
        requant=rq,
        **kw,
    )


@functools.partial(jax.jit, static_argnames=("plan", "requant_shift"))
def run_conv2d(
    plan: ConvLayerPlan,
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    requant: Optional[Tuple[jax.Array, jax.Array]] = None,
    *,
    requant_shift: Optional[int] = None,
) -> jax.Array:
    """Run one planned conv (+ fused epilogue).  THE dispatch site.

    x (N,H,W,C), w (K,K,C/groups,F) -> (N,H_O,W_O,F); the substrate,
    decimation mode, and tiling all come from ``plan`` (static).  ``bias``
    / ``requant_shift`` / ``requant`` are the runtime epilogue inputs —
    per-channel requant calibrations are traced (F,) int32 array pairs.
    """
    if plan.substrate in ("oracle", "f32exact"):
        # f32exact: integer convs run exactly on the fast f32 conv path
        # (channel-chunked, bit-identical — ref.conv2d_exact_f32); float
        # inputs degrade to the plain oracle inside the helper.  Sub-8-bit
        # weight plans tighten the chunking bound: the int5 MSR lane's
        # decompressed operands satisfy |w| <= 2^w_bits - 1 = 31, widening
        # the lossless channel chunks ~4x (DESIGN.md §9.3).
        oracle = plan.substrate == "oracle"
        s = plan.stride
        kw = dict(padding=plan.padding, groups=plan.groups)
        if not oracle and plan.w_bits < 8:
            kw["w_abs_max"] = (1 << plan.w_bits) - 1
        conv = ref.conv2d_ref if oracle else ref.conv2d_exact_f32
        if plan.decimate:
            full = conv(x, w, stride=1, **kw)
            out = full[:, ::s, ::s, :]
        else:
            out = conv(x, w, stride=s, **kw)
        return apply_epilogue(out, bias, plan.relu, requant_shift, requant)

    if plan.groups == 1:
        out = _group_call(plan, x, w, bias, requant, requant_shift)
    else:
        cg = x.shape[-1] // plan.groups
        F = w.shape[-1]
        fg = F // plan.groups

        def rq_slice(g):
            # Per-group requant slices (scalars broadcast to (F,) first so
            # per-channel and per-tensor calibrations both land per group).
            if requant is None:
                return None
            m, s = requant
            m = jnp.broadcast_to(jnp.asarray(m, jnp.int32), (F,))
            s = jnp.broadcast_to(jnp.asarray(s, jnp.int32), (F,))
            return (m[g * fg : (g + 1) * fg], s[g * fg : (g + 1) * fg])

        outs = [
            _group_call(
                plan,
                x[..., g * cg : (g + 1) * cg],
                w[..., g * fg : (g + 1) * fg],
                None if bias is None else bias[g * fg : (g + 1) * fg],
                rq_slice(g),
                requant_shift,
            )
            for g in range(plan.groups)
        ]
        out = jnp.concatenate(outs, axis=-1)
    if plan.decimate:
        out = apply_epilogue(out, bias, plan.relu, requant_shift, requant)
    return out


def _run_conv2d_on_mesh(plan: ConvLayerPlan, x, w, bias, requant):
    """:func:`run_conv2d`, mapped over the batch when an active mesh
    splits it.

    XLA cannot partition a Pallas (Mosaic) kernel, so under a mesh whose
    data axes shard the batch each device runs the kernels on its own
    images (``jax.shard_map``).  Weights, bias and requant pairs enter
    replicated (sharded ones are gathered), so the transpose sums the
    weight and bias grads over the batch axes — the data-parallel
    all-reduce.  Oracle-type substrates stay with XLA's partitioner.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import current_mesh_context, logical_to_spec

    ctx = current_mesh_context()
    if ctx is None or plan.substrate not in ("pallas", "interpret"):
        return run_conv2d(plan, x, w, bias, requant)
    xspec = logical_to_spec(("batch", None, None, None), x.shape, ctx)
    if xspec[0] is None:
        return run_conv2d(plan, x, w, bias, requant)
    return jax.shard_map(
        functools.partial(run_conv2d, plan),
        mesh=ctx.mesh,
        in_specs=(xspec, P(), P(), P()),
        out_specs=xspec,
        check_vma=False,
    )(x, w, bias, requant)


def run_conv_layer(plan: ConvLayerPlan, p, x: jax.Array) -> jax.Array:
    """One model conv block: planned conv -> shard -> optional 2x2 pool.

    ``p``: {"kernel": (K,K,C/groups,F) [, "bias": (F,) , "requant":
    ((F,), (F,)) int32 calibration]} — params-borne requant takes
    precedence (the per-channel calibrated int8 datapath).
    """
    from repro.distributed.sharding import shard

    w = p["kernel"]
    if jnp.issubdtype(x.dtype, jnp.floating):
        w = w.astype(x.dtype)
    x = _run_conv2d_on_mesh(plan, x, w, p.get("bias"), p.get("requant"))
    x = shard(x, "batch", "img_h", "img_w", "cout")
    if plan.pool:
        x = max_pool2x2(x)
    return x


# ---------------------------------------------------------------------------
# Model-level entry points (consumed via ModelPlan)
# ---------------------------------------------------------------------------


def forward(plan: ModelPlan, params, images: jax.Array) -> jax.Array:
    """images (B,H,W,C) float -> logits (B, n_classes) through the planned
    conv stack (fused bias+ReLU epilogues) and the FC head."""
    x = images
    for i, lp in enumerate(plan.layers):
        x = run_conv_layer(lp, params["conv"][i], x)
    return _dense_head(params["fc"], x.reshape(x.shape[0], -1))


def _dense_head(fc, x: jax.Array, precision=None) -> jax.Array:
    """The FC head's dense layers on (N, K) features: bias, and ReLU
    between layers."""
    for j, layer in enumerate(fc):
        x = jnp.dot(x, layer["kernel"].astype(x.dtype), precision=precision)
        x = x + layer["bias"].astype(x.dtype)
        if j < len(fc) - 1:
            x = jax.nn.relu(x)
    return x


#: Rows of one block of the serving head (:func:`serve_head`): the
#: largest bucket the serving launchers and the benchmark use, so each of
#: their buckets is one block and reads the head's weights once.
HEAD_ROWS = 64


#: Zero rows below each head block's output, so that a real slice
#: follows the block at every N (:func:`serve_head`).
_TAIL_ROWS = 8


def head_rows(n: int) -> int:
    """Rows the serving head runs for ``n`` images: whole
    :data:`HEAD_ROWS` blocks, the last one zero-padded."""
    return -(-int(n) // HEAD_ROWS) * HEAD_ROWS


def serve_head(fc, x: jax.Array) -> jax.Array:
    """The FC head over (N, K) features, in fixed (HEAD_ROWS, K) blocks.

    Row i of a dense product does not depend on the other rows, but its
    bits depend on how the compiler tiles the product, and a v5e picks
    the tiling from the product's shape and from what feeds and consumes
    it.  So every image's head runs inside the same (HEAD_ROWS, K) block
    computation at every N (one block for N <= HEAD_ROWS, else a
    ``lax.map`` over blocks), and each block meets the same neighbours:
    a barrier on its input, and zero rows padded below its output behind
    a barrier, so that a real slice, never the program's output, follows
    at every N.  Without them a full block's last product was tiled
    otherwise than a padded one's, and bucket 64 differed from bucket 1
    in the last bits.  Each block reads the weights once; ``HIGHEST``
    keeps the head at f32 accuracy on the MXU.
    """
    n = x.shape[0]
    rows = head_rows(n)
    blocks = jnp.pad(x, ((0, rows - n), (0, 0))).reshape(rows // HEAD_ROWS, HEAD_ROWS, -1)
    block = functools.partial(_head_block, fc)
    if rows == HEAD_ROWS:
        return block(blocks[0])[:n]
    out = jax.lax.map(block, blocks)[:, :HEAD_ROWS]
    return out.reshape(rows, -1)[:n]


def _head_block(fc, h: jax.Array) -> jax.Array:
    """One (HEAD_ROWS, K) block of :func:`serve_head`, with _TAIL_ROWS
    zero rows below its result."""
    h = jax.lax.optimization_barrier(h)
    out = _dense_head(fc, h, precision=jax.lax.Precision.HIGHEST)
    return jax.lax.optimization_barrier(jnp.pad(out, ((0, _TAIL_ROWS), (0, 0))))


def serve_forward(plan: ModelPlan, params, images: jax.Array) -> jax.Array:
    """Batch-invariant :func:`forward` for the serving executables.

    The conv stack is already batch-invariant (each image's kernels see
    only that image); the FC head is made so by :func:`serve_head`'s
    fixed row blocks.  Serving guarantees bucketed == unbatched per image
    bit-exactly.  The head is ~1% of VGG-16's MACs, but its weights are
    494 MB in f32: read once per image they took over a quarter of a
    bucket's device time on a v5e, so they are read once per block.
    """
    x = images
    for i, lp in enumerate(plan.layers):
        x = run_conv_layer(lp, params["conv"][i], x)
    with jax.named_scope("serve_head"):
        return serve_head(params["fc"], x.reshape(x.shape[0], -1))


def loss(plan: ModelPlan, params, batch):
    logits = forward(plan, params, batch["images"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    ce = -ll.mean()
    acc = (logits.argmax(-1) == batch["labels"]).mean()
    return ce, {"ce": ce, "acc": acc}


def _int8_forward(
    plan: ModelPlan,
    qparams,
    images_u8: jax.Array,
    requant_shifts: Optional[Sequence[int]] = None,
    requant: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
) -> Tuple[jax.Array, List[jax.Array]]:
    """Shared int8 datapath: returns (final int32 psums, dynamic shifts).

    ``requant_shifts`` fuses calibrated power-of-two shifts into the
    kernel; ``requant`` fuses calibrated arbitrary-scale (mult, shift)
    pairs (per-tensor scalars or per-channel (F,) arrays) instead.  The
    shifts list collects the per-layer power-of-two shifts actually used
    on the dynamic (uncalibrated) path — traced scalars, so calibration
    must run this eagerly to concretize them.
    """
    assert requant_shifts is None or requant is None
    x = images_u8
    shifts: List[jax.Array] = []
    layers = plan.int8.layers
    n = len(layers)
    for i, lp in enumerate(layers):
        w = qparams["conv"][i]["kernel"]
        last = i == n - 1
        if requant is not None and not last:
            # Calibrated arbitrary scale: conv + ReLU + multiplier+shift
            # requant in one kernel pass (DESIGN.md §4).
            x = run_conv2d(lp, x, w, None, tuple(requant[i]))
        elif requant_shifts is not None and not last:
            # Calibrated shift: conv + ReLU + requant in one kernel pass.
            x = run_conv2d(lp, x, w, None, None, requant_shift=int(requant_shifts[i]))
        else:
            psum = run_conv2d(lp, x, w, None, None)
            if last:
                return psum, shifts
            # power-of-two requantize back to uint8 for the next layer
            amax = jnp.maximum(psum.max().astype(jnp.float32), 1.0)
            shift = jnp.maximum(jnp.ceil(jnp.log2(amax / 255.0)), 0)
            shift = shift.astype(jnp.int32)
            shifts.append(shift)
            x = jnp.clip(psum >> shift, 0, 255).astype(jnp.uint8)
        if lp.pool:
            x = max_pool2x2(x)
    return x, shifts


def forward_int8(
    plan: ModelPlan,
    qparams,
    images_u8: jax.Array,
    requant_shifts: Optional[Sequence[int]] = None,
    requant: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
) -> jax.Array:
    """uint8 NHWC images through the planned integer TrIM datapath.

    Each layer: uint8 x int8 -> int32 psums (exact), ReLU in int32 (fused
    into the kernel flush), then requantize to uint8 for the next layer —
    fully fused when calibrated shifts/pairs are supplied (see
    ``calibrate_requant_shifts`` / ``calibrate_requant``).  Returns the
    final int32 feature map (pre-classifier).
    """
    return _int8_forward(plan, qparams, images_u8, requant_shifts, requant)[0]


def calibrate_requant_shifts(plan: ModelPlan, qparams, sample_u8) -> List[int]:
    """Derive static per-layer power-of-two requant shifts from a sample
    batch (the engine's offline output-stage calibration).  Runs the
    dynamic datapath eagerly (not under jit) to concretize the shifts."""
    return [int(s) for s in _int8_forward(plan, qparams, sample_u8)[1]]


def calibrate_requant(
    plan: ModelPlan, qparams, sample_u8, per_channel: bool = True
) -> List[Tuple[jax.Array, jax.Array]]:
    """Arbitrary-scale calibration: per-layer (mult, shift) pairs.

    Maps each non-last layer's observed post-ReLU psum range [0, amax]
    onto [0, 255] with ``scale = 255 / amax`` encoded as ``m * 2**-s``
    (``kernels.requant.scale_to_mult_shift``; DESIGN.md §4).
    ``per_channel=True`` calibrates one scale per output channel.  Runs
    eagerly; the returned (F,) int32 pairs make
    ``forward_int8(..., requant=...)`` fully fused.
    """
    from repro.kernels.requant import scale_to_mult_shift

    x = sample_u8
    pairs: List[Tuple[jax.Array, jax.Array]] = []
    for i, lp in enumerate(plan.int8.layers[:-1]):
        w = qparams["conv"][i]["kernel"]
        psum = run_conv2d(lp, x, w, None, None)
        axes = (0, 1, 2) if per_channel else None
        amax = np.maximum(np.asarray(psum.max(axis=axes), np.float64), 1.0)
        m, s = scale_to_mult_shift(255.0 / amax)
        F = w.shape[-1]
        m = jnp.broadcast_to(jnp.asarray(m, jnp.int32), (F,))
        s = jnp.broadcast_to(jnp.asarray(s, jnp.int32), (F,))
        pairs.append((m, s))
        # Propagate through the exact fixed-point datapath the fused
        # forward will run, so downstream layers calibrate on what they
        # will actually see.
        x = requant_mult_shift(psum, m, s).astype(jnp.uint8)
        if lp.pool:
            x = max_pool2x2(x)
    return pairs


def forward_int5(
    plan: ModelPlan,
    qparams,
    images_u8: jax.Array,
    requant: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
) -> jax.Array:
    """uint8 images through the MSR-compressed int5 weight lane.

    ``qparams["conv"][i]`` carries ``{"kernel", "shift"}`` from
    ``nn.conv.quantize_cnn_int5``: the small decompressed operand ``w5``
    (int8, ``|w5| <= 31``) and the per-output-channel MSR exponent ``e``
    with ``w_hat == w5 << e`` (``core.trim.quant.msr_operand``).  The conv
    kernels multiply by ``w5`` unchanged — the exponent is applied
    losslessly after the fact:

    - calibrated path (``requant`` from :func:`calibrate_requant_int5`):
      the pairs already absorbed ``e`` via ``fold_shift_into_requant``, so
      each non-last layer is one fused conv+ReLU+requant pass, same as
      int8;
    - dynamic path (no ``requant``): the psums are explicitly left-shifted
      by ``e`` before the power-of-two requantize (batch-dependent, not
      servable — mirrors the int8 dynamic path);
    - the last layer always returns ``psums << e``: full-scale int32
      features comparable to the int8 lane's output.

    Bit-exactness contract: with calibrated pairs this equals running
    :func:`forward_int8` on the decompressed weights ``w5 << e`` exactly
    (DESIGN.md §9.3 has the proof sketch; tests/test_int5.py checks it).
    """
    x = images_u8
    layers = plan.int5.layers
    n = len(layers)
    for i, lp in enumerate(layers):
        p = qparams["conv"][i]
        w5 = p["kernel"]
        e = jnp.asarray(p["shift"], jnp.int32)
        last = i == n - 1
        if requant is not None and not last:
            x = run_conv2d(lp, x, w5, None, tuple(requant[i]))
        else:
            psum = jnp.left_shift(run_conv2d(lp, x, w5, None, None), e)
            if last:
                return psum
            amax = jnp.maximum(psum.max().astype(jnp.float32), 1.0)
            shift = jnp.maximum(jnp.ceil(jnp.log2(amax / 255.0)), 0)
            x = jnp.clip(psum >> shift.astype(jnp.int32), 0, 255).astype(jnp.uint8)
        if lp.pool:
            x = max_pool2x2(x)
    return x


def calibrate_requant_int5(
    plan: ModelPlan, qparams, sample_u8, per_channel: bool = True
) -> List[Tuple[jax.Array, jax.Array]]:
    """(mult, shift) calibration for the int5 lane, exponent pre-folded.

    Same procedure as :func:`calibrate_requant` — map each non-last
    layer's observed full-scale psum range onto [0, 255] — except the
    psums observed here are ``psum5 << e`` (the MSR exponent restored),
    and the resulting pairs are returned with ``e`` folded back in
    (``core.trim.quant.fold_shift_into_requant``), so the fused kernels
    can consume the raw ``w5`` psums directly:
    ``requant(psum5, m, s - e) == requant(psum5 << e, m, s)`` exactly.
    """
    from repro.core.trim.quant import fold_shift_into_requant
    from repro.kernels.requant import scale_to_mult_shift

    x = sample_u8
    pairs: List[Tuple[jax.Array, jax.Array]] = []
    for i, lp in enumerate(plan.int5.layers[:-1]):
        p = qparams["conv"][i]
        w5 = p["kernel"]
        e = np.asarray(p["shift"], np.int32)
        psum5 = run_conv2d(lp, x, w5, None, None)
        full = jnp.left_shift(psum5, jnp.asarray(e))
        axes = (0, 1, 2) if per_channel else None
        amax = np.maximum(np.asarray(full.max(axis=axes), np.float64), 1.0)
        m, s = scale_to_mult_shift(255.0 / amax)
        F = w5.shape[-1]
        m = np.broadcast_to(np.asarray(m, np.int32), (F,))
        s = np.broadcast_to(np.asarray(s, np.int32), (F,))
        mf, sf = fold_shift_into_requant(m, s, e)
        mf = jnp.asarray(mf, jnp.int32)
        sf = jnp.asarray(sf, jnp.int32)
        pairs.append((mf, sf))
        x = requant_mult_shift(psum5, mf, sf).astype(jnp.uint8)
        if lp.pool:
            x = max_pool2x2(x)
    return pairs


# ---------------------------------------------------------------------------
# Serving executables: ahead-of-time compiles per (plan, batch, datapath)
# ---------------------------------------------------------------------------


#: Compile ledger: (plan, batch, datapath) -> number of times an executable
#: was actually built.  ``lru_cache`` hits never touch it, so the serving
#: tests can assert each (ModelPlan, bucket) executable compiled exactly
#: once across a whole request stream.
EXECUTABLE_COMPILES: Dict[Tuple[ModelPlan, int, str], int] = {}

#: Fault-injection seam for the serving chaos plane (DESIGN.md §11):
#: when set, called as ``hook(plan, batch, datapath)`` at the top of
#: :func:`executable_for` *before* any work — raising there simulates a
#: rejected/failed AOT compile.  ``lru_cache`` never caches a call that
#: raised, so a bounded retry after a transient fault recompiles cleanly.
#: Installed/cleared by ``ServeEngine.warmup`` only; always ``None`` in
#: production.
COMPILE_FAULT_HOOK = None


def _donate_images_argnums() -> tuple:
    """Donation spec for the serving executables' image argument.

    The serving flush worker stages each bucket with ``jax.device_put``
    and never reuses the staged buffer, so donating it lets the runtime
    recycle that transfer target in place — the staging half of the
    transfer/compute overlap.  CPU jaxlib does not implement input
    donation (it warns and ignores), so donation is requested only on
    backends that honor it.
    """
    import jax

    return (1,) if jax.default_backend() in ("gpu", "tpu", "cuda", "rocm") else ()


@functools.lru_cache(maxsize=None)
def executable_for(plan: ModelPlan, batch: int, datapath: str = "float"):
    """AOT-compile ``plan``'s forward for one static batch size (cached).

    ``jax.jit(...).lower(shapes).compile()`` pins the executable to exactly
    ``(batch, H, W, C)`` inputs — a serving loop calling it structurally
    cannot retrace, which is the no-retrace-under-load guarantee
    (DESIGN.md §8).  Returns the compiled callable:

    - ``datapath="float"``: ``compiled(params, images_f32) -> logits``
      (param shapes via ``jax.eval_shape`` over ``init_cnn``; runs
      :func:`serve_forward` — the batch-invariant head — so per-image
      outputs are bit-identical across buckets);
    - ``datapath="int8"``: ``compiled(qparams, images_u8, requant) ->
      int32 feature map`` — ``requant`` is the calibrated per-layer list of
      per-channel (mult, shift) int32 pairs and is *required*: the
      uncalibrated dynamic-shift path requantizes off ``psum.max()`` over
      the whole batch, so its per-image outputs depend on batch
      composition and can never be served from padded buckets;
    - ``datapath="int5"``: same signature as int8, but ``qparams`` carries
      the MSR operand + per-channel exponent pair per layer
      (``quantize_cnn_int5``) and ``requant`` the exponent-folded pairs
      from ``calibrate_requant_int5`` (DESIGN.md §9.3).

    Cached per (plan, batch, datapath); equal plans share executables.
    """
    if COMPILE_FAULT_HOOK is not None:
        COMPILE_FAULT_HOOK(plan, batch, datapath)
    if datapath not in ("float", "int8", "int5"):
        raise ValueError(
            f"datapath {datapath!r} not in ('float', 'int8', 'int5')")
    cfg = plan.cfg
    H, W = cfg.input_hw
    C = plan.layers[0].c_in
    batch = int(batch)
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if datapath == "float":
        from repro.nn.conv import init_cnn

        pshapes = jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.PRNGKey(0))
        img = jax.ShapeDtypeStruct((batch, H, W, C), jnp.float32)
        compiled = (
            jax.jit(lambda p, x: serve_forward(plan, p, x),
                    donate_argnums=_donate_images_argnums())
            .lower(pshapes, img)
            .compile()
        )
    else:
        # Integer param shapes come straight from the config (quantize_cnn
        # concretizes scales, so it is not eval_shape-able).  The int5 lane
        # adds the per-channel MSR exponent array next to each kernel.
        def _qshape(l):
            d = {"kernel": jax.ShapeDtypeStruct((l.K, l.K, l.M, l.N), jnp.int8)}
            if datapath == "int5":
                d["shift"] = jax.ShapeDtypeStruct((l.N,), jnp.int32)
            return d

        qshapes = {"conv": [_qshape(l) for l in cfg.layers]}
        rshapes = [
            (
                jax.ShapeDtypeStruct((l.N,), jnp.int32),
                jax.ShapeDtypeStruct((l.N,), jnp.int32),
            )
            for l in cfg.layers[:-1]
        ]
        img = jax.ShapeDtypeStruct((batch, H, W, C), jnp.uint8)
        if datapath == "int5":
            fwd = lambda qp, x, rq: forward_int5(plan, qp, x, requant=rq)  # noqa: E731
        else:
            fwd = lambda qp, x, rq: forward_int8(plan, qp, x, requant=rq)  # noqa: E731
        compiled = (
            jax.jit(fwd, donate_argnums=_donate_images_argnums())
            .lower(qshapes, img, rshapes)
            .compile()
        )
    key = (plan, batch, datapath)
    EXECUTABLE_COMPILES[key] = EXECUTABLE_COMPILES.get(key, 0) + 1
    return compiled
