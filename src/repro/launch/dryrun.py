import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count on first init). Tests may scale the dry-run down via env var —
# still set before jax initializes:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell with 512 placeholder host devices, and record the artifacts the
roofline analysis reads (memory_analysis, cost_analysis, collective bytes).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
      --shape train_4k --multi-pod                              # one cell
  ... --out experiments/dryrun                                  # artifacts

Every failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the system, not in the dry-run.
"""
import argparse
import json
import math
import time
import traceback
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, shape_cells
from repro.configs.base import ModelConfig, ShapeCell
from repro.distributed.sharding import activate_mesh, fsdp_pspec, param_pspec
from repro.distributed.steps import (StepConfig, batch_pspec, cache_pspec,
                                     make_decode_step, make_prefill_step,
                                     make_train_step, state_pspec,
                                     train_state_shapes, _to_shardings)
from repro.launch.hlo_stats import (collective_stats,
                                    hbm_bytes_estimate,
                                    total_collective_bytes)
from repro.launch.mesh import (HBM_BW, HBM_BYTES, ICI_BW, PEAK_FLOPS_BF16,
                               make_mesh, make_production_mesh)
from repro.launch.specs import input_specs, model_flops
from repro.nn.models import build_model


def scaled_mesh(multi_pod: bool):
    """Production mesh, or a proportionally scaled one when the dry-run
    device count was overridden (REPRO_DRYRUN_DEVICES, tests only)."""
    n = len(jax.devices())
    if n >= 512:
        return make_production_mesh(multi_pod=multi_pod)
    # scale down, keeping the axis structure
    if multi_pod:
        pod = 2
        rest = n // pod
        side = int(math.sqrt(rest))
        while rest % side:
            side -= 1
        return make_mesh((pod, rest // side, side),
                         ("pod", "data", "model"))
    side = int(math.sqrt(n))
    while n % side:
        side -= 1
    return make_mesh((n // side, side), ("data", "model"))


def build_cell(cfg: ModelConfig, cell: ShapeCell, mesh, fsdp: bool = False,
               accum: int = 1):
    """Returns (fn, example_args (SDS pytrees), in_shardings, out_shardings)."""
    tp = mesh.shape["model"]
    model = build_model(cfg, tp=tp)
    # "2d" serve layout: batch replicated over data (only pod, if present);
    # the data axis carries the weight 2D shard + the KV sequence shard.
    serve_2d = (cell.kind == "decode"
                and getattr(cfg, "decode_kv_seqshard", "") == "2d")
    extra_rules = {"batch": (("pod",),)} if serve_2d else None
    with activate_mesh(mesh, extra_rules=extra_rules) as ctx:
        if cell.kind == "train":
            batch = input_specs(cfg, model, cell)
            shapes = train_state_shapes(model)
            sspec = state_pspec(shapes, ctx, fsdp=fsdp)
            bspec = batch_pspec(batch, ctx)
            fn = make_train_step(model, StepConfig(accum=accum), mesh)
            args = (shapes, batch)
            in_sh = (_to_shardings(sspec, mesh), _to_shardings(bspec, mesh))
            out_sh = (_to_shardings(sspec, mesh), None)
        elif cell.kind == "prefill":
            batch, cache = input_specs(cfg, model, cell)
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            pspec = (fsdp_pspec if fsdp else param_pspec)(pshapes, ctx)
            bspec = batch_pspec(batch, ctx)
            cspec = cache_pspec(cache, ctx)
            fn = make_prefill_step(model)
            args = (pshapes, batch, cache)
            in_sh = (_to_shardings(pspec, mesh), _to_shardings(bspec, mesh),
                     _to_shardings(cspec, mesh))
            out_sh = (None, _to_shardings(cspec, mesh))
        elif cell.kind == "decode":
            batch, cache = input_specs(cfg, model, cell)
            pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            if serve_2d and fsdp:
                # 2D weight sharding: TP dim over model, other dim over
                # data (pod stays free for batch) -> partial-sum matmuls
                pspec = fsdp_pspec(pshapes, ctx, dp_axes=("data",))
            else:
                pspec = (fsdp_pspec if fsdp else param_pspec)(pshapes, ctx)
            cspec = cache_pspec(cache, ctx)
            fn = make_decode_step(model)
            args = (pshapes, batch["token"], cache, batch["pos"])
            tok_sh = NamedSharding(mesh, batch_pspec(
                {"t": batch["token"]}, ctx)["t"])
            in_sh = (_to_shardings(pspec, mesh), tok_sh,
                     _to_shardings(cspec, mesh), NamedSharding(mesh, P()))
            out_sh = (None, _to_shardings(cspec, mesh))
        else:
            raise ValueError(cell.kind)
    return fn, args, in_sh, out_sh


def _cell_costs(cfg: ModelConfig, cell: ShapeCell, mesh,
                fsdp: bool = False) -> Dict[str, float]:
    """flops / bytes / collective_bytes of one compiled variant."""
    fn, args, in_sh, out_sh = build_cell(cfg, cell, mesh, fsdp=fsdp)
    with activate_mesh(mesh), mesh:
        compiled = jax.jit(fn, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*args).compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = collective_stats(hlo)
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0)),
           "collective_bytes": total_collective_bytes(hlo)}
    for op, s in stats.items():
        out[f"coll_{op}"] = s["bytes"]
    return out


def calibrated_costs(cfg: ModelConfig, cell: ShapeCell, mesh,
                     fsdp: bool = False) -> Dict[str, float]:
    """Exact per-device cost of the FULL model, extrapolated linearly from
    small *unrolled* variants (XLA cost_analysis counts a while/scan body
    once, so the scanned artifact's numbers undercount by the trip count;
    layer costs are exactly additive, so const + n_periods * per_period
    from unrolled 2- and 4-period compiles recovers the true total)."""
    from repro.nn.models import decoder_schedule
    period = len(decoder_schedule(cfg)[0])

    def variant(n_lay: int, n_enc: int = 0) -> Dict[str, float]:
        over = {"n_layers": n_lay, "scan_layers": False}
        if cfg.family == "encdec":
            over["n_enc_layers"] = n_enc
        return _cell_costs(cfg.with_overrides(**over), cell, mesh,
                           fsdp=fsdp)

    def keys_of(*ds):
        return sorted(set().union(*[d.keys() for d in ds]))

    if cfg.family == "encdec":
        c22 = variant(2, 2)
        c42 = variant(4, 2)
        c24 = variant(2, 4)
        out = {}
        for k in keys_of(c22, c42, c24):
            per_dec = (c42.get(k, 0) - c22.get(k, 0)) / 2
            per_enc = (c24.get(k, 0) - c22.get(k, 0)) / 2
            const = c22.get(k, 0) - 2 * per_dec - 2 * per_enc
            out[k] = max(const + cfg.n_layers * per_dec
                         + cfg.n_enc_layers * per_enc, 0.0)
        return out
    c2 = variant(2 * period)
    c4 = variant(4 * period)
    n_periods = cfg.n_layers // period
    out = {}
    for k in keys_of(c2, c4):
        per = (c4.get(k, 0) - c2.get(k, 0)) / 2
        const = c2.get(k, 0) - 2 * per
        out[k] = max(const + n_periods * per, 0.0)
    return out


def run_cell(arch: str, cell: ShapeCell, multi_pod: bool,
             save_hlo: Optional[str] = None, fsdp: bool = False,
             cfg_overrides: Optional[Dict[str, Any]] = None,
             accum: int = 1) -> Dict[str, Any]:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.with_overrides(**cfg_overrides)
    fsdp = fsdp or getattr(cfg, "fsdp", False)
    mesh = scaled_mesh(multi_pod)
    chips = mesh.size
    record: Dict[str, Any] = {
        "arch": arch, "shape": cell.name, "kind": cell.kind,
        "mesh": {ax: int(mesh.shape[ax]) for ax in mesh.axis_names},
        "chips": chips, "multi_pod": multi_pod,
    }
    record["fsdp"] = fsdp
    record["accum"] = accum
    if cfg_overrides:
        record["cfg_overrides"] = {k: str(v) for k, v in
                                   cfg_overrides.items()}
    t0 = time.time()
    fn, args, in_sh, out_sh = build_cell(cfg, cell, mesh, fsdp=fsdp,
                                         accum=accum)
    with activate_mesh(mesh), mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    record["lower_s"] = round(t_lower, 2)
    record["compile_s"] = round(t_compile, 2)

    try:
        mem = compiled.memory_analysis()
        record["memory"] = hbm_bytes_estimate(mem)
    except Exception as e:  # pragma: no cover
        record["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        record["cost"] = {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and k in ("flops", "bytes accessed",
                                    "transcendentals", "optimal_seconds")}
    except Exception as e:  # pragma: no cover
        record["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    record["collectives"] = collective_stats(hlo)
    record["collective_bytes_raw"] = total_collective_bytes(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    del hlo

    # --- calibrated per-device costs (scan-trip-count-exact) ---
    calib = calibrated_costs(cfg, cell, mesh, fsdp=fsdp)
    record["cost_calibrated"] = calib
    record["collective_bytes"] = calib.get("collective_bytes", 0.0)

    # --- roofline terms (per step, v5e constants) ---
    # cost_analysis on a partitioned module reports PER-DEVICE numbers
    flops = calib.get("flops", 0.0)
    bytes_acc = calib.get("bytes", 0.0)
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_acc / HBM_BW
    collective_s = record["collective_bytes"] / ICI_BW
    mf = model_flops(cfg, cell)
    record["roofline"] = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": max((("compute", compute_s), ("memory", memory_s),
                         ("collective", collective_s)),
                        key=lambda kv: kv[1])[0],
        "model_flops_total": mf,
        "model_flops_per_chip": mf / chips,
        "useful_flops_ratio": (mf / chips) / flops if flops else 0.0,
        "step_time_bound_s": max(compute_s, memory_s, collective_s),
    }
    # per-device HBM check: XLA's peak-memory estimate (live-set peak over
    # the buffer assignment) where available; else arguments + outputs.
    # CPU buffer assignment lacks TPU-grade fusion, so this is conservative.
    mem = record.get("memory", {})
    peak = mem.get("peak_memory_in_bytes", 0)
    args_b = mem.get("argument_size_in_bytes", 0)
    per_dev = max(peak, args_b) or (args_b + mem.get(
        "output_size_in_bytes", 0))
    record["fits_hbm"] = bool(per_dev <= HBM_BYTES) if per_dev else None
    record["per_device_bytes"] = per_dev
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape cell (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run single-pod AND multi-pod")
    ap.add_argument("--out", default="experiments/dryrun",
                    help="artifact directory")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--fsdp", action="store_true",
                    help="FSDP/ZeRO-3 parameter sharding over the DP axes")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        cells = [c for c in shape_cells(cfg)
                 if args.shape is None or c.name == args.shape]
        for cell in cells:
            for mp in meshes:
                tag = f"{arch}__{cell.name}__{'multi' if mp else 'single'}"
                hlo_path = (os.path.join(args.out, tag + ".hlo.txt")
                            if args.save_hlo else None)
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rec = run_cell(arch, cell, mp, save_hlo=hlo_path,
                                   fsdp=args.fsdp)
                except Exception as e:
                    print(f"[dryrun] FAIL {tag}: {e}")
                    traceback.print_exc()
                    failures.append(tag)
                    continue
                finally:
                    jax.clear_caches()   # keep single-process RSS bounded
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"[dryrun]   ok: compile {rec['compile_s']:.1f}s  "
                      f"compute {r['compute_s']*1e3:.2f}ms  "
                      f"memory {r['memory_s']*1e3:.2f}ms  "
                      f"collective {r['collective_s']*1e3:.2f}ms  "
                      f"dominant={r['dominant']}  "
                      f"useful={r['useful_flops_ratio']:.2f}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print(f"[dryrun] all cells passed.")


if __name__ == "__main__":
    main()
