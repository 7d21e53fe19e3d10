"""Per-layer plan autotuner: search schedules, persist winners (DESIGN.md §7).

The TrIM papers' central claim is that the *schedule* — tiling, blocking,
and which engine runs the layer — determines memory traffic and therefore
throughput; the companion dataflow-modelling paper derives per-layer
optimal schedules analytically.  This module finds them empirically: given
one conv layer's static description (the same arguments
:func:`repro.engine.plan.plan_conv_layer` takes), it

1. enumerates a candidate schedule space — substrate switches (pallas /
   oracle / f32exact), and for the Pallas substrate a one-factor-at-a-time
   sweep of ``tile_h`` / ``tile_w`` / ``block_c`` / ``block_f`` with
   ``pick_tile_w``'s VMEM cost model (``_vmem_bytes``) pruning width tiles
   that cannot fit the budget;
2. compiles each candidate once through the one dispatch site
   (``execute.run_conv2d``) and times it with warmup + median-of-k;
3. gates candidates on *bit-identity* with the default plan's output
   (schedule changes timing, not math — spatial re-tiling and exact
   integer substrates pass, accumulation-order changes on floats are
   rejected unless ``allow_inexact=True``);
4. returns the winner, preferring the default unless a candidate beats it
   by more than ``MIN_GAIN`` — a tuned plan is never slower than the
   default it replaces;
5. persists the winner in a JSON plan cache under ``tuned_plans/`` keyed
   by (layer geometry, dtype byte sizes, epilogue kind, emulate_hw) inside
   a per-(backend, device kind) cache file stamped with
   ``PLAN_CACHE_VERSION``.

``plan_conv_layer`` consults :func:`tuned_schedule` transparently when the
policy requests ``tuning="cached"`` (miss -> default plan) or
``tuning="auto"`` (tune-on-miss, then persist), so models planned via
``plan_model`` run each layer on its measured-best schedule.
"""

from __future__ import annotations

import json
import os
import re
import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import execute
from repro.engine.plan import plan_conv_layer, plan_model
from repro.engine.policy import RESOLVED_SUBSTRATES, ExecutionPolicy, on_tpu
from repro.kernels.trim_conv2d import _vmem_bytes, conv2d_geom

#: Bump when plan semantics change (new schedule fields, kernel geometry
#: changes, …): cache files with a different version are ignored with a
#: warning, so stale winners never silently misconfigure new kernels.
#: v2: layer keys gained the batch axis ``n{N}`` — a schedule measured at
#: N=1 is not a winner under a loaded server's batch buckets.
#: v3: layer keys gained the weight-width axis ``w{bits}`` — the int5 MSR
#: lane (DESIGN.md §9.3) shares layer geometry with int8 but widens the
#: f32exact chunking ~4x, so its winners are measured separately.
PLAN_CACHE_VERSION = 3

#: The policy fields a persisted schedule may override.
SCHEDULE_FIELDS = ("substrate", "tile_h", "tile_w", "block_c", "block_f")

#: A non-default candidate must beat the default by this fraction to be
#: shipped — inside the margin the default wins (measurement noise must
#: never make a tuned plan slower than the default it replaces).
MIN_GAIN = 0.05

#: One-factor-at-a-time sweep values for the Pallas schedule knobs.
TILE_H_CANDIDATES = (4, 8, 16, 32)
BLOCK_CANDIDATES = (64, 128, 256)


# ---------------------------------------------------------------------------
# Cache keys and the JSON plan cache
# ---------------------------------------------------------------------------


def cache_dir() -> str:
    """Plan-cache directory (``REPRO_TUNED_PLANS_DIR``, default
    ``tuned_plans/`` under the current working directory)."""
    return os.environ.get("REPRO_TUNED_PLANS_DIR", "tuned_plans")


def device_kind() -> str:
    return jax.devices()[0].device_kind


def cache_path() -> str:
    """One cache file per (backend, device kind) — measured schedules only
    transfer within one hardware class."""
    slug = re.sub(r"[^A-Za-z0-9_.-]+", "-", device_kind())
    return os.path.join(cache_dir(), f"{jax.default_backend()}-{slug}.json")


def layer_key(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int,
    padding: Optional[int],
    groups: int,
    relu: bool,
    has_bias: bool,
    requant_kind: Optional[str],
    in_sz: int,
    w_sz: int,
    out_sz: int,
    emulate_hw: bool,
    batch: int = 1,
    w_bits: int = 8,
) -> str:
    """The layer's plan-cache key: geometry + dtype byte sizes + epilogue.

    ``batch`` is the batch size the schedule was measured at — a serving
    bucket runs N images per call, and the winning schedule can differ
    from the N=1 winner (the serving core plans each bucket with its own
    batch, so each bucket gets its own persisted winner).  ``w_bits`` is
    the stored weight width (8, or 5 for the MSR lane): the sub-8-bit
    operands change the f32exact chunk count, so the lanes tune apart.

    Backend, device kind, and code version live at the cache-file level
    (:func:`cache_path`, ``PLAN_CACHE_VERSION``) — together they complete
    the key the issue tracker calls (layer geometry, dtype, epilogue kind,
    batch, backend + device kind, code version).
    """
    pad = "same" if padding is None else str(padding)
    epi = f"{int(relu)}{int(has_bias)}.{requant_kind or 'none'}"
    return (
        f"conv2d n{batch} h{x_hw[0]}x{x_hw[1]} c{c_in} k{k} f{c_out} "
        f"s{stride} p{pad} g{groups} ep{epi} "
        f"sz{in_sz}.{w_sz}.{out_sz} emu{int(emulate_hw)} w{w_bits}"
    )


#: In-process mirror of the cache files: path -> {key -> entry}.  A second
#: lookup in the same process never re-reads the file, and a lookup after
#: :func:`store_schedule` sees the new entry without one either.
_LOADED: Dict[str, Dict[str, dict]] = {}


def reset_cache() -> None:
    """Forget in-process plan-cache state (tests, cache-dir switches).

    Also drops the plan lru caches: cached ``ConvLayerPlan``s bake tuned
    schedules in, so they must be re-resolved after the cache changes.
    """
    _LOADED.clear()
    plan_conv_layer.cache_clear()
    plan_model.cache_clear()


def _load_plans(path: str) -> Dict[str, dict]:
    if path in _LOADED:
        return _LOADED[path]
    plans: Dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            version = data.get("version") if isinstance(data, dict) else None
            if version != PLAN_CACHE_VERSION:
                raise ValueError(f"cache version {version!r} != {PLAN_CACHE_VERSION}")
            plans = data.get("plans")
            if not isinstance(plans, dict):
                raise ValueError("'plans' is not a mapping")
        except Exception as e:  # corrupt/stale cache: degrade, don't crash
            warnings.warn(
                f"tuned-plan cache {path} is unreadable ({e}); "
                "falling back to default plans",
                RuntimeWarning,
                stacklevel=3,
            )
            plans = {}
    _LOADED[path] = plans
    return plans


def _valid_schedule(sched: object) -> bool:
    if not isinstance(sched, dict) or set(sched) != set(SCHEDULE_FIELDS):
        return False
    if sched["substrate"] not in RESOLVED_SUBSTRATES:
        return False
    for field in ("tile_h", "block_c", "block_f"):
        if not isinstance(sched[field], int) or sched[field] < 1:
            return False
    tw = sched["tile_w"]
    return tw is None or (isinstance(tw, int) and tw >= 1)


def load_schedule(key: str) -> Optional[Dict[str, object]]:
    """The persisted winning schedule for ``key``, or None on a miss (or on
    an invalid entry, which warns and degrades to a miss)."""
    entry = _load_plans(cache_path()).get(key)
    if entry is None:
        return None
    sched = entry.get("schedule") if isinstance(entry, dict) else None
    if not _valid_schedule(sched):
        warnings.warn(
            f"tuned-plan cache entry for {key!r} is invalid; "
            "falling back to the default plan",
            RuntimeWarning,
            stacklevel=3,
        )
        return None
    return dict(sched)


def store_schedule(key: str, entry: Dict[str, object]) -> None:
    """Persist one tuning result (atomic write) and refresh the in-process
    mirror + plan lru caches so the winner is visible immediately."""
    path = cache_path()
    plans = dict(_load_plans(path))
    plans[key] = entry
    payload = {
        "version": PLAN_CACHE_VERSION,
        "backend": jax.default_backend(),
        "device_kind": device_kind(),
        "plans": plans,
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    _LOADED[path] = plans
    plan_conv_layer.cache_clear()
    plan_model.cache_clear()


# ---------------------------------------------------------------------------
# Candidate enumeration (cost-model pruned)
# ---------------------------------------------------------------------------


def tile_w_candidates(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int,
    padding: Optional[int],
    groups: int,
    tile_h: int,
    block_c: int,
    block_f: int,
    in_sz: int,
    w_sz: int,
    out_sz: int,
    vmem_budget: int,
) -> List[Optional[int]]:
    """Divisor-aligned ``tile_w`` picks that fit the VMEM budget.

    Sizes each pick on the kernel's own geometry (``conv2d_geom``) with
    ``pick_tile_w``'s cost conventions (2 input passes for the full-width
    halo layout, 4 for the column-tiled one) so the pruner and the kernel
    agree on what fits; candidates are ceil(W_O / n) for n = 1, 2, 4, 8, …
    rounded up to 8-sublane multiples.  ``None`` (let ``pick_tile_w``
    auto-size at plan time) is always the first candidate.
    """
    cg, fg = c_in // groups, c_out // groups

    def geom(tile_w):
        return conv2d_geom((1, *x_hw, cg), (k, k, cg, fg), stride=stride,
                           padding=padding, tile_h=tile_h, tile_w=tile_w,
                           block_c=block_c, block_f=block_f)

    W_O = geom(1).W_O
    cands: List[Optional[int]] = [None]
    n = 1
    while n <= W_O:
        g = geom(W_O if n == 1 else -(-(-(-W_O // n)) // 8) * 8)
        passes = (4 if g.tiled else 2) if g.has_halo else 1
        cost = _vmem_bytes(cols=g.CB, Cb=g.Cb, Fb=g.Fb, K=g.Kf, TH=g.TH,
                           TW=g.TW, passes=passes, in_sz=in_sz, w_sz=w_sz,
                           out_sz=out_sz)
        if cost <= vmem_budget and g.TW not in cands:
            cands.append(g.TW)
        if not g.tiled and n > 1:
            break
        n *= 2
    return cands[:4]


def candidate_policies(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int = 1,
    padding: Optional[int] = None,
    groups: int = 1,
    in_sz: int = 4,
    w_sz: int = 4,
    out_sz: int = 4,
    policy: ExecutionPolicy = ExecutionPolicy(),
    include_pallas: Optional[bool] = None,
) -> List[ExecutionPolicy]:
    """Enumerate candidate policies for one layer (default first).

    Substrate moves: the resolved default always leads; integer layers
    (``in_sz == 1``) add "f32exact" (the exact chunked-f32 oracle); the
    plain "oracle" is added when the default is something else (so small
    layers where XLA wins get routed there per-layer).  When the compiled
    Pallas kernel is available (on TPU, or ``include_pallas=True`` in
    tests) the Pallas schedule knobs get a one-factor-at-a-time sweep —
    ``tile_h``, cost-model-pruned ``tile_w``, ``block_c``/``block_f`` caps
    — rather than a full cross product (the analytical model says the
    knobs are near-separable; a full product is measurement budget, not
    insight).  "interpret" is a debugging substrate and is never searched:
    a policy already resolved to it keeps its single default candidate.
    """
    base = policy.resolve().with_overrides(tuning="off")
    cands = [base]
    if base.substrate == "interpret":
        return cands
    if in_sz == 1 and base.substrate != "f32exact":
        cands.append(base.with_overrides(substrate="f32exact"))
    if base.substrate != "oracle":
        cands.append(base.with_overrides(substrate="oracle"))
    if include_pallas is None:
        include_pallas = on_tpu()
    if include_pallas:
        p = k // 2 if padding is None else padding
        H_O = (x_hw[0] + 2 * p - k) // stride + 1
        pallas = base.with_overrides(substrate="pallas")
        if base.substrate != "pallas":
            cands.append(pallas)
        for th in TILE_H_CANDIDATES:
            if th != pallas.tile_h and th <= max(H_O, 1):
                cands.append(pallas.with_overrides(tile_h=th))
        for tw in tile_w_candidates(
            x_hw,
            c_in,
            k,
            c_out,
            stride=stride,
            padding=padding,
            groups=groups,
            tile_h=pallas.tile_h,
            block_c=pallas.block_c,
            block_f=pallas.block_f,
            in_sz=in_sz,
            w_sz=w_sz,
            out_sz=out_sz,
            vmem_budget=pallas.vmem_budget,
        ):
            if tw != pallas.tile_w:
                cands.append(pallas.with_overrides(tile_w=tw))
        for bc in BLOCK_CANDIDATES:
            if bc != pallas.block_c and bc <= c_in // groups:
                cands.append(pallas.with_overrides(block_c=bc))
        for bf in BLOCK_CANDIDATES:
            if bf != pallas.block_f and bf <= c_out // groups:
                cands.append(pallas.with_overrides(block_f=bf))
    return list(dict.fromkeys(cands))


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _measure_plan(
    plan,
    *,
    in_sz: int,
    warmup: int = 1,
    reps: int = 5,
    batch: int = 1,
) -> Tuple[float, np.ndarray]:
    """Compile ``plan`` once via ``execute.run_conv2d``, then time it.

    Returns (median wall-clock in us over ``reps`` timed calls after
    ``warmup`` extra calls, output as a numpy array for the bit-identity
    gate).  Inputs are synthesized from the plan — ``batch`` images of
    uint8 x / int8 w for the integer lane (``in_sz == 1``), bf16/f32
    otherwise — so a schedule tuned for a serving bucket is measured at
    that bucket's batch size.
    """
    key = jax.random.PRNGKey(0)
    x_shape = (int(batch), plan.x_hw[0], plan.x_hw[1], plan.c_in)
    w_shape = (plan.k, plan.k, plan.c_in // plan.groups, plan.c_out)
    F = plan.c_out
    requant = None
    requant_shift = None
    bias = None
    if in_sz == 1:
        # Sub-8-bit plans are measured with representative small-magnitude
        # operands: the f32exact substrate's chunk count (its cost) depends
        # on the |w| bound the plan's w_bits guarantees.
        wmax = (1 << plan.w_bits) - 1 if plan.w_bits < 8 else 127
        x = jax.random.randint(key, x_shape, 0, 255, jnp.uint8)
        w = jax.random.randint(
            jax.random.fold_in(key, 1), w_shape, -wmax, wmax, jnp.int8
        )
        if plan.requant_kind == "mult_shift":
            requant = (
                jnp.full((F,), 16384, jnp.int32),
                jnp.full((F,), 20, jnp.int32),
            )
        elif plan.requant_kind == "shift":
            requant_shift = 8
        if plan.has_bias:
            bias = jnp.zeros((F,), jnp.int32)
    else:
        dt = jnp.bfloat16 if in_sz == 2 else jnp.float32
        x = jax.random.normal(key, x_shape, dt)
        w = jax.random.normal(jax.random.fold_in(key, 1), w_shape, dt)
        if plan.has_bias:
            bias = jax.random.normal(jax.random.fold_in(key, 2), (F,), dt)

    def call():
        return execute.run_conv2d(
            plan, x, w, bias, requant, requant_shift=requant_shift
        )

    out = jax.block_until_ready(call())  # compile + identity-gate output
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(call())
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6, np.asarray(out)


def aggregate_pair(ta, tb):
    """THE drift-robust A/B statistic, shared by the tuner and the
    benchmarks (``benchmarks.run._timeit_pair``).

    Machine load, cgroup CPU throttling, and thermal drift can skew
    sequential timings by 2-3x within one process.  Two *adjacent* calls
    share one throttle state, so each round's ``tb/ta`` is clean even
    when absolute times move 3x between rounds: the median of the
    per-round ratios is the decision statistic, the per-arm mins are the
    least-contended wall-clock observations.  ``ta``/``tb`` are the
    paired per-round timings (same units in = same units out); returns
    (t_a, t_b, ratio_b_over_a).
    """
    ratio = float(np.median([b / a for a, b in zip(ta, tb)]))
    return float(np.min(ta)), float(np.min(tb)), ratio


def _measure_pair(plan_a, plan_b, *, in_sz: int, reps: int = 5, batch: int = 1):
    """Alternate single-rep measurements of two plans; aggregate with
    :func:`aggregate_pair`.  Returns (us_a, us_b, ratio_b_over_a)."""
    _measure_plan(plan_a, in_sz=in_sz, warmup=0, reps=1, batch=batch)  # warm
    _measure_plan(plan_b, in_sz=in_sz, warmup=0, reps=1, batch=batch)
    ta, tb = [], []
    for _ in range(max(reps, 1)):
        ta.append(_measure_plan(plan_a, in_sz=in_sz, warmup=0, reps=1, batch=batch)[0])
        tb.append(_measure_plan(plan_b, in_sz=in_sz, warmup=0, reps=1, batch=batch)[0])
    return aggregate_pair(ta, tb)


# ---------------------------------------------------------------------------
# Tuning
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CandidateTiming:
    schedule: Dict[str, object]
    us: float
    exact: bool


@dataclass(frozen=True)
class TuneResult:
    """One layer's tuning outcome (also what gets persisted)."""

    key: str
    schedule: Dict[str, object]
    us: float
    us_default: float
    candidates: Tuple[CandidateTiming, ...]
    cached: bool = False

    @property
    def speedup(self) -> float:
        """Default-vs-tuned ratio (>= 1.0: the winner is never slower)."""
        return self.us_default / self.us if self.us else float("inf")


def _schedule_of_plan(plan) -> Dict[str, object]:
    """The persistable schedule a plan encodes.

    ``tile_w`` persists the explicit override (None = auto-pick at plan
    time); ``block_*`` persist the per-group-capped values — re-applying a
    capped value as the policy cap resolves to the identical plan.
    """
    return {
        "substrate": plan.substrate,
        "tile_h": plan.tile_h,
        "tile_w": plan.tile_w_arg,
        "block_c": plan.block_c,
        "block_f": plan.block_f,
    }


def tune_conv_layer(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int = 1,
    padding: Optional[int] = None,
    groups: int = 1,
    relu: bool = False,
    has_bias: bool = False,
    requant_kind: Optional[str] = None,
    in_sz: int = 4,
    w_sz: int = 4,
    out_sz: int = 4,
    w_bits: int = 8,
    policy: ExecutionPolicy = ExecutionPolicy(),
    batch: int = 1,
    warmup: int = 1,
    reps: int = 5,
    allow_inexact: bool = False,
    persist: bool = True,
    force: bool = False,
) -> TuneResult:
    """Tune one conv layer: measure the candidates, pick + persist a winner.

    Unless ``force``, a persisted winner for this key is returned as-is
    (``cached=True``, no re-measurement).  ``batch`` is part of the cache
    key and sizes the synthesized measurement inputs (the serving buckets
    tune per batch size).  Candidates whose output is not bit-identical to
    the default plan's are discarded unless ``allow_inexact`` (then a
    float-tolerance ``allclose`` gate applies instead); among survivors
    the fastest wins, but only if it beats the default by more than
    ``MIN_GAIN`` — otherwise the default ships.
    """
    kw = dict(
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
    )
    key = layer_key(
        x_hw, c_in, k, c_out, emulate_hw=policy.resolve().emulate_hw, batch=batch, **kw
    )
    if not force:
        entry = _load_plans(cache_path()).get(key)
        sched = load_schedule(key)
        if sched is not None:
            return TuneResult(
                key=key,
                schedule=sched,
                us=float(entry.get("us", 0.0)),
                us_default=float(entry.get("us_default", 0.0)),
                candidates=(),
                cached=True,
            )
    base = policy.resolve().with_overrides(tuning="off")

    def build(pol):
        return plan_conv_layer(x_hw, c_in, k, c_out, policy=pol, **kw)

    policies = candidate_policies(
        x_hw,
        c_in,
        k,
        c_out,
        stride=stride,
        padding=padding,
        groups=groups,
        in_sz=in_sz,
        w_sz=w_sz,
        out_sz=out_sz,
        policy=base,
    )
    # Distinct policies can resolve to the same plan (caps, degenerate
    # tiles) — measure each distinct *plan* once.
    plans = list(dict.fromkeys(build(p) for p in policies))
    default_plan = plans[0]
    us_default, ref_out = _measure_plan(
        default_plan, in_sz=in_sz, warmup=warmup, reps=reps, batch=batch
    )
    timings = [CandidateTiming(_schedule_of_plan(default_plan), us_default, True)]
    best_plan, best_us = default_plan, us_default
    for plan in plans[1:]:
        try:
            us, out = _measure_plan(
                plan, in_sz=in_sz, warmup=warmup, reps=reps, batch=batch
            )
        except Exception as e:
            # Candidates come from an *estimated* cost model; one whose
            # real footprint the compiler rejects (VMEM overflow, …) is
            # discarded like an inexact one, not allowed to abort the
            # whole search.
            warnings.warn(
                f"autotune candidate {_schedule_of_plan(plan)} failed to "
                f"compile/run ({e}); discarded",
                RuntimeWarning,
                stacklevel=2,
            )
            continue
        if out.dtype == ref_out.dtype and np.array_equal(out, ref_out):
            exact = True
        elif allow_inexact and np.allclose(
            out.astype(np.float64),
            ref_out.astype(np.float64),
            rtol=1e-4,
            atol=1e-4,
        ):
            exact = False
        else:
            continue  # changes math: never a legal schedule move
        timings.append(CandidateTiming(_schedule_of_plan(plan), us, exact))
        if us < best_us:
            best_plan, best_us = plan, us
    if best_plan is not default_plan:
        # Drift-robust verification of the win: re-measure the default and
        # the challenger interleaved before shipping a non-default plan —
        # the never-slower rule must hold against a paired ratio, not
        # against two timings taken minutes apart on a drifting machine.
        try:
            us_d2, us_b2, ratio = _measure_pair(
                default_plan, best_plan, in_sz=in_sz, reps=reps, batch=batch
            )
        except Exception:  # challenger died on re-measure: default ships
            ratio = float("inf")
        if ratio > 1 - MIN_GAIN:
            best_plan, best_us = default_plan, us_default
        else:
            best_us, us_default = us_b2, us_d2
    schedule = _schedule_of_plan(best_plan)
    result = TuneResult(
        key=key,
        schedule=schedule,
        us=best_us,
        us_default=us_default,
        candidates=tuple(timings),
    )
    if persist:
        store_schedule(
            key,
            {
                "schedule": schedule,
                "us": round(best_us, 1),
                "us_default": round(us_default, 1),
                "speedup": round(result.speedup, 3),
                "candidates": len(plans),
                "reps": reps,
            },
        )
    return result


def tuned_schedule(
    x_hw: Tuple[int, int],
    c_in: int,
    k: int,
    c_out: int,
    *,
    stride: int,
    padding: Optional[int],
    groups: int,
    relu: bool,
    has_bias: bool,
    requant_kind: Optional[str],
    in_sz: int,
    w_sz: int,
    out_sz: int,
    w_bits: int = 8,
    policy: ExecutionPolicy,
    batch: int = 1,
) -> Optional[Dict[str, object]]:
    """The schedule ``plan_conv_layer`` should apply under ``policy.tuning``.

    "cached": the persisted winner or None (default plan).  "auto": the
    persisted winner, tuning (measuring) once on a miss and persisting.
    ``batch`` selects the batch-specific winner (a plan built for a
    serving bucket looks up the schedule measured at that bucket's N).
    """
    kw = dict(
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
    )
    key = layer_key(
        x_hw, c_in, k, c_out, emulate_hw=policy.resolve().emulate_hw, batch=batch, **kw
    )
    sched = load_schedule(key)
    if sched is None and policy.tuning == "auto":
        sched = tune_conv_layer(
            x_hw, c_in, k, c_out, policy=policy, batch=batch, **kw
        ).schedule
    return sched


def tune_model(
    cfg,
    policy: ExecutionPolicy = ExecutionPolicy(),
    c_in: Optional[int] = None,
    datapath: str = "float",
    **tune_kw,
) -> List[Tuple[str, TuneResult]]:
    """Tune every conv layer of a ``CNNConfig`` (the ``plan_model`` walk).

    Returns ``[(layer label, TuneResult), ...]``; repeated identical
    layers hit the plan cache after their first tuning.  ``tune_kw``
    forwards to :func:`tune_conv_layer` (``reps``, ``force``, ``batch`` —
    pass the serving bucket's batch size to tune the model for it, …).
    """
    if datapath not in ("float", "int8", "int5"):
        raise ValueError(
            f"datapath {datapath!r} not in ('float', 'int8', 'int5')")
    int8 = datapath in ("int8", "int5")
    pol = policy.resolve()
    results = []
    c = cfg.layers[0].M if c_in is None else int(c_in)
    last_i = len(cfg.layers) - 1
    for i, l in enumerate(cfg.layers):
        res = tune_conv_layer(
            (l.H_I, l.W_I),
            c,
            l.K,
            l.N,
            stride=l.stride,
            padding=l.padding,
            groups=c // l.M,
            relu=True,
            has_bias=not int8,
            requant_kind="mult_shift" if int8 and i != last_i else None,
            in_sz=1 if int8 else 4,
            w_sz=1 if int8 else 4,
            out_sz=(4 if i == last_i else 1) if int8 else 4,
            w_bits=5 if datapath == "int5" else 8,
            policy=pol,
            **tune_kw,
        )
        results.append((f"{cfg.name}/{l.name}.{datapath}", res))
        c = l.N
    return results
