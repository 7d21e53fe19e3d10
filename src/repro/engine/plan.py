"""Static execution plans: compile the TrIM kernel configuration once.

A :class:`ConvLayerPlan` is the fully-resolved static schedule for one conv
layer — substrate, decimation mode, tiling geometry (``conv2d_geom`` /
``pick_tile_w``), per-group block caps, and the fused-epilogue descriptor —
computed once from an :class:`~repro.engine.policy.ExecutionPolicy` and the
layer shape, then handed to the executor (``repro.engine.execute``) and to
``jax.jit`` as a hashable static argument.

:func:`plan_model` walks a ``CNNConfig``'s layer stack (tracking the
running channel count for the grouped AlexNet two-tower layers) and emits a
:class:`ModelPlan` whose ``forward`` / ``loss`` / ``quantize`` /
``calibrate*`` / ``forward_int8`` entry points run the whole network off
the per-layer plans — ``ConvNet``, ``build_model``, the launchers, and the
benchmarks all consume plans instead of re-deriving kernel kwargs.

Both plan types are frozen dataclasses of plain values: hashable,
comparable by value, and cached (``lru_cache``), so rebuilding a plan from
an equal config + policy hits every downstream cache — the planner's own,
the ``make_trim_conv2d_vjp`` handle cache, and ``jax.jit``'s static-arg
trace cache.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from repro.engine.policy import ExecutionPolicy
from repro.kernels.trim_conv2d import Conv2DGeom, conv2d_geom
from repro.kernels.trim_conv2d_vjp import make_trim_conv2d_vjp


@dataclass(frozen=True)
class ConvLayerPlan:
    """Fully-resolved static schedule for one TrIM conv layer.

    ``substrate`` is already resolved ("pallas" | "oracle" | "interpret" —
    the policy's dispatch rule ran at plan time).  ``tile_w`` is the
    output-width tile ``pick_tile_w`` chose for one group's kernel call
    (``geom.n_wt == 1`` means the degenerate single-W-block schedule the
    paper shapes keep); ``tile_w_arg`` preserves an explicit user override
    (None lets each kernel invocation auto-pick with its actual dtypes —
    identical to ``tile_w`` for the planned dtype).  ``block_c`` /
    ``block_f`` are capped to the per-group channel/filter counts.
    ``geom`` is the per-group kernel geometry — computed at stride 1 when
    ``emulate_hw`` decimation replays the FPGA's strided-layer schedule.
    """

    x_hw: Tuple[int, int]
    c_in: int
    k: int
    c_out: int
    stride: int
    padding: Optional[int]
    groups: int
    relu: bool
    pool: bool
    has_bias: bool
    requant_kind: Optional[str]
    substrate: str
    emulate_hw: bool
    tile_h: int
    tile_w: int
    tile_w_arg: Optional[int]
    block_c: int
    block_f: int
    vmem_budget: int
    epilogue: str
    geom: Conv2DGeom
    #: Stored weight width in bits. 8 = plain int8 weights; 5 = the MSR
    #: compressed lane (sign + 4-bit most-significant-run codes,
    #: ``core.trim.quant.msr_compress`` — DESIGN.md §9.3), whose runtime
    #: operand is int8 with ``|w| <= 31``, widening the f32exact lossless
    #: chunks (`run_conv2d` derives the bound from this field).  Part of
    #: the plan's identity: tuned-plan cache keys carry it.
    w_bits: int = 8
    #: True when this schedule came from the autotuner's plan cache
    #: (``repro.engine.autotune``, DESIGN.md §7) rather than the policy
    #: defaults.  Metadata, not schedule: ``compare=False`` keeps a tuned
    #: plan whose winning schedule IS the default equal (and hash-equal)
    #: to the default plan, so ``jax.jit`` reuses one executable for both.
    tuned: bool = field(default=False, compare=False)

    @property
    def decimate(self) -> bool:
        """FPGA-faithful strided-layer replay: stride-1 sweep + decimation
        + unfused epilogue (paper §V)."""
        return self.emulate_hw and self.stride > 1

    @property
    def interpret(self) -> bool:
        return self.substrate == "interpret"

    def vjp(self, has_bias: Optional[bool] = None):
        """The ``jax.custom_vjp``-wrapped fused forward for this schedule
        (float Pallas path).  Cached per static config in
        ``make_trim_conv2d_vjp`` — equal plans share one handle."""
        return make_trim_conv2d_vjp(
            stride=self.stride,
            padding=self.padding,
            relu=self.relu,
            has_bias=self.has_bias if has_bias is None else has_bias,
            tile_h=self.tile_h,
            tile_w=self.tile_w_arg,
            block_c=self.block_c,
            block_f=self.block_f,
            vmem_budget=self.vmem_budget,
            interpret=self.interpret,
        )

    def describe(self) -> Dict[str, object]:
        """Compact schedule record (benchmark artifacts, dry-run JSON)."""
        d = {
            "substrate": self.substrate,
            "tile_w": self.tile_w,
            "n_wt": self.geom.n_wt,
            "epilogue": self.epilogue,
        }
        if self.w_bits != 8:
            d["w_bits"] = self.w_bits
        if self.tuned:
            d["tuned"] = True
        return d


@functools.lru_cache(maxsize=None)
def plan_conv_layer(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int = 1,
    padding: Optional[int] = None,
    groups: int = 1,
    relu: bool = False,
    pool: bool = False,
    has_bias: bool = False,
    requant_kind: Optional[str] = None,
    in_sz: int = 4,
    w_sz: int = 4,
    out_sz: int = 4,
    w_bits: int = 8,
    policy: ExecutionPolicy = ExecutionPolicy(),
    batch: int = 1,
) -> ConvLayerPlan:
    """Resolve one layer's static schedule under ``policy`` (cached).

    ``x_hw`` is the layer's input spatial extent, ``c_in`` the *total*
    input channel count (all groups), ``c_out`` the total filter count.
    ``requant_kind`` describes the planned fused requantization (None |
    "shift" | "mult_shift") — the actual multiplier/shift values stay
    runtime arguments (per-channel calibrations are traced arrays).
    ``in_sz``/``w_sz``/``out_sz`` are element byte sizes for the VMEM
    width-tile auto-pick (pass the real itemsizes for non-f32 datapaths).
    ``batch`` only selects which batch-specific autotuner winner applies
    (tuned-plan cache keys carry the batch axis); it is not a field of the
    resulting plan — kernels take the batch from the runtime array.

    When ``policy.tuning`` is "cached" or "auto" the persisted autotuner
    winner for this layer's cache key is applied transparently on top of
    the policy (substrate + tile/block schedule — DESIGN.md §7); a cache
    miss under "cached" falls back to the default plan, under "auto" it
    tunes once (measures the candidate schedules) and persists the winner.
    Tuning composes with ``substrate="auto"`` only: an explicitly pinned
    substrate (``--substrate oracle/interpret/...``) is a stronger request
    than the cache — the persisted winner was measured against the auto
    default, so it is NOT applied over a pin (the plan resolves as if
    tuning were off).  Tuning happens here, at plan time — plan eagerly
    (outside ``jit``) when tuning is on.
    """
    pol = policy.resolve()
    tuned = False
    if pol.tuning != "off" and policy.substrate == "auto":
        from repro.engine import autotune  # deferred: autotune imports us

        schedule = autotune.tuned_schedule(
            x_hw,
            c_in,
            k,
            c_out,
            stride=stride,
            padding=padding,
            groups=groups,
            relu=relu,
            has_bias=has_bias,
            requant_kind=requant_kind,
            in_sz=in_sz,
            w_sz=w_sz,
            out_sz=out_sz,
            w_bits=w_bits,
            policy=pol,
            batch=batch,
        )
        pol = pol.with_overrides(tuning="off")
        if schedule is not None:
            pol = pol.with_overrides(**schedule)
            tuned = True
    cg = c_in // groups
    fg = c_out // groups
    decimate = pol.emulate_hw and stride > 1
    # The kernel folds stride phases into channels (S*S*cg of them).
    fold = 1 if decimate else stride * stride
    block_c = min(pol.block_c, cg * fold)
    block_f = min(pol.block_f, fg)
    geom = conv2d_geom(
        (1, x_hw[0], x_hw[1], cg),
        (k, k, cg, fg),
        stride=1 if decimate else stride,
        padding=padding,
        tile_h=pol.tile_h,
        tile_w=pol.tile_w,
        block_c=block_c,
        block_f=block_f,
        in_sz=in_sz,
        w_sz=w_sz,
        out_sz=out_sz,
        vmem_budget=pol.vmem_budget,
    )
    parts = []
    if has_bias:
        parts.append("bias")
    if relu:
        parts.append("relu")
    if requant_kind == "shift":
        parts.append("requant_shift")
    elif requant_kind == "mult_shift":
        parts.append("requant")
    epilogue = "+".join(parts) if parts else "linear"
    if decimate:
        epilogue = f"decimate->{epilogue}"
    return ConvLayerPlan(
        x_hw=x_hw,
        c_in=c_in,
        k=k,
        c_out=c_out,
        stride=stride,
        padding=padding,
        groups=groups,
        relu=relu,
        pool=pool,
        has_bias=has_bias,
        requant_kind=requant_kind,
        substrate=pol.substrate,
        emulate_hw=pol.emulate_hw,
        tile_h=pol.tile_h,
        tile_w=geom.TW,
        tile_w_arg=pol.tile_w,
        block_c=block_c,
        block_f=block_f,
        vmem_budget=pol.vmem_budget,
        epilogue=epilogue,
        geom=geom,
        w_bits=w_bits,
        tuned=tuned,
    )


@dataclass(frozen=True)
class ModelPlan:
    """Per-layer plans + entry points for one CNN under one policy.

    Execution entry points delegate to ``repro.engine.execute`` (lazy
    imports keep the module graph acyclic); the plan itself is pure static
    data and safe to close over under ``jax.jit``.
    """

    cfg: object
    policy: ExecutionPolicy
    layers: Tuple[ConvLayerPlan, ...]
    #: Batch size the per-layer tuned schedules were selected for (the
    #: autotuner's cache keys carry a batch axis).  Kernels still take the
    #: batch from the runtime array — this only picks which persisted
    #: winners the layer plans baked in, so a serving bucket's plan can
    #: differ from the N=1 plan.
    batch: int = 1

    def init(self, key):
        from repro.nn.conv import init_cnn

        return init_cnn(key, self.cfg)

    def forward(self, params, images):
        from repro.engine import execute

        return execute.forward(self, params, images)

    def loss(self, params, batch):
        from repro.engine import execute

        return execute.loss(self, params, batch)

    def quantize(self, params):
        from repro.nn.conv import quantize_cnn

        return quantize_cnn(params, self.cfg)

    def forward_int8(self, qparams, images_u8, requant_shifts=None, requant=None):
        from repro.engine import execute

        return execute.forward_int8(
            self, qparams, images_u8, requant_shifts=requant_shifts, requant=requant
        )

    def calibrate_requant_shifts(self, qparams, sample_u8):
        from repro.engine import execute

        return execute.calibrate_requant_shifts(self, qparams, sample_u8)

    def calibrate_requant(self, qparams, sample_u8, per_channel=True):
        from repro.engine import execute

        return execute.calibrate_requant(
            self, qparams, sample_u8, per_channel=per_channel
        )

    def quantize_int5(self, params, compensate=True):
        from repro.nn.conv import quantize_cnn_int5

        return quantize_cnn_int5(params, self.cfg, compensate=compensate)

    def forward_int5(self, qparams, images_u8, requant=None):
        from repro.engine import execute

        return execute.forward_int5(self, qparams, images_u8, requant=requant)

    def calibrate_requant_int5(self, qparams, sample_u8, per_channel=True):
        from repro.engine import execute

        return execute.calibrate_requant_int5(
            self, qparams, sample_u8, per_channel=per_channel
        )

    @property
    def int8(self) -> "ModelPlan":
        """This model's integer-datapath sibling plan: same architecture +
        policy, but bias-free fused-requant epilogues and uint8/int8 byte
        sizes for the VMEM tile pick — what ``forward_int8`` actually runs
        and what its benchmark/dry-run records should describe."""
        return plan_model(
            self.cfg,
            self.policy,
            c_in=self.layers[0].c_in,
            datapath="int8",
            batch=self.batch,
        )

    @property
    def int5(self) -> "ModelPlan":
        """The MSR-compressed weight lane's sibling plan (DESIGN.md §9.3):
        identical to :attr:`int8` except every layer plan carries
        ``w_bits=5``, so ``run_conv2d`` widens the f32exact chunk bound for
        the ``|w| <= 31`` decompressed operands and the autotuner keys the
        lane separately.  What ``forward_int5`` actually runs."""
        return plan_model(
            self.cfg,
            self.policy,
            c_in=self.layers[0].c_in,
            datapath="int5",
            batch=self.batch,
        )

    def executable_for(self, batch: int, datapath: str = "float"):
        """Ahead-of-time-compiled model forward for one static batch size.

        The serving hook (DESIGN.md §8): ``jax.jit(...).lower(...).compile()``
        over this plan's forward at exactly ``(batch, H, W, C)``, cached per
        (plan, batch, datapath) in ``execute.executable_for`` — a request
        stream served through the returned callable structurally cannot
        retrace.  "float" → ``compiled(params, images_f32)``;
        "int8" → ``compiled(qparams, images_u8, requant)`` with calibrated
        per-layer (mult, shift) pairs (the dynamic-shift requant path is
        batch-dependent and therefore not servable from buckets);
        "int5" → same signature, ``qparams`` additionally carrying the
        per-channel MSR exponents and ``requant`` the exponent-folded pairs
        from ``calibrate_requant_int5`` (DESIGN.md §9.3).
        """
        from repro.engine import execute

        return execute.executable_for(self, batch, datapath)

    def describe(self) -> Tuple[Dict[str, object], ...]:
        return tuple(lp.describe() for lp in self.layers)


@functools.lru_cache(maxsize=None)
def plan_model(
    cfg,
    policy: ExecutionPolicy = ExecutionPolicy(),
    c_in: Optional[int] = None,
    datapath: str = "float",
    layer_substrates: Optional[Tuple[Optional[str], ...]] = None,
    batch: int = 1,
) -> ModelPlan:
    """Compile a ``CNNConfig`` into a :class:`ModelPlan` (cached).

    Walks ``cfg.layers`` tracking the running channel count ``c`` (grouped
    AlexNet two-tower layers have ``groups = c // layer.M``), resolving one
    :class:`ConvLayerPlan` per layer under the policy.  ``c_in``
    overrides the first layer's input channel count (defaults to
    ``cfg.layers[0].M``).  ``datapath`` is "float" (biased conv + fused
    bias/ReLU, f32 byte sizes), "int8" (the paper's integer inference
    lane: bias-free, fused mult+shift requant on every non-last layer,
    uint8/int8 byte sizes — the last layer emits raw int32 psums), or
    "int5" (the MSR-compressed weight lane: identical layer shapes and
    epilogues but ``w_bits=5`` on every layer plan — DESIGN.md §9.3).
    ``batch`` selects batch-specific autotuner winners per layer (serving
    buckets plan at their own N); the default 1 keeps historical plans.

    ``layer_substrates`` pins per-layer substrates (a tuple with one entry
    per conv layer; ``None`` entries keep the policy's choice), so a
    ModelPlan can be heterogeneous — small layers on the XLA oracle, wide
    layers on Pallas, integer layers on f32exact.  Plans resolved under
    ``policy.tuning != "off"`` become heterogeneous the same way, from the
    autotuner's per-layer cache instead of an explicit tuple (a pinned
    layer beats the cache, like a pinned ``--substrate`` does).

    The policy is passed to the per-layer planner *unresolved*: each
    ``plan_conv_layer`` call resolves it, and tuning only composes with
    ``substrate="auto"`` — resolving here would erase that marker.
    """
    if datapath not in ("float", "int8", "int5"):
        raise ValueError(
            f"datapath {datapath!r} not in ('float', 'int8', 'int5')")
    if layer_substrates is not None and len(layer_substrates) != len(cfg.layers):
        raise ValueError(
            f"layer_substrates has {len(layer_substrates)} entries for "
            f"{len(cfg.layers)} conv layers"
        )
    int8 = datapath in ("int8", "int5")
    plans = []
    c = cfg.layers[0].M if c_in is None else int(c_in)
    last_i = len(cfg.layers) - 1
    for i, l in enumerate(cfg.layers):
        lpol = policy
        if layer_substrates is not None and layer_substrates[i] is not None:
            lpol = policy.with_overrides(substrate=layer_substrates[i])
        plans.append(
            plan_conv_layer(
                (l.H_I, l.W_I),
                c,
                l.K,
                l.N,
                stride=l.stride,
                padding=l.padding,
                groups=c // l.M,
                relu=True,
                pool=i in cfg.pool_after,
                has_bias=not int8,
                requant_kind="mult_shift" if int8 and i != last_i else None,
                in_sz=1 if int8 else 4,
                w_sz=1 if int8 else 4,
                out_sz=(4 if i == last_i else 1) if int8 else 4,
                w_bits=5 if datapath == "int5" else 8,
                policy=lpol,
                batch=batch,
            )
        )
        c = l.N
    return ModelPlan(cfg=cfg, policy=policy, layers=tuple(plans), batch=int(batch))
