import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Bonus dry-run: the paper's own CNN workloads (VGG-16 / AlexNet) as a
pod-scale data-parallel training step through the TrIM conv path.

  PYTHONPATH=src python -m repro.launch.dryrun_cnn --arch vgg16

Execution flags (``--substrate`` / ``--emulate-hw`` / ``--int8`` /
``--tuning``) come from the shared launcher parent (``launch.cli``) and
map onto one ``ExecutionPolicy``; the resolved per-layer plan (substrate,
width tile, epilogue kind, tuned flag) is recorded in the emitted JSON —
with ``--tuning cached`` each layer runs the autotuner's persisted winner
(DESIGN.md §7).  ``--int8`` additionally
compiles the integer inference datapath with the arbitrary-scale fused
requant epilogue (DESIGN.md §4) and emits a second roofline record;
``--int5`` does the same for the MSR-compressed weight lane
(DESIGN.md §9.3).
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import CNN_REGISTRY
from repro.distributed.sharding import activate_mesh
from repro.engine import plan_model
from repro.launch.cli import execution_parent, policy_from_args
from repro.launch.dryrun import scaled_mesh
from repro.launch.hlo_stats import (collective_stats,
                                    hbm_bytes_estimate,
                                    total_collective_bytes)
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.nn.conv import cnn_forward_int8, cnn_loss, init_cnn
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.core.trim.model import layer_ops


def _int_record(cfg, args, mesh, dp, policy, datapath="int8"):
    """Compile an integer inference forward (fused multiplier+shift requant
    in every non-last layer) and derive its roofline.  Requant constants
    are placeholder calibrations — the dry-run only studies the compiled
    schedule, not accuracy.  ``datapath="int5"`` compiles the MSR weight
    lane instead (per-channel exponent operands, DESIGN.md §9.3)."""
    H, W = cfg.input_hw
    int5 = datapath == "int5"
    qshapes = {"conv": [
        dict({"kernel": jax.ShapeDtypeStruct((l.K, l.K, l.M, l.N),
                                             jnp.int8)},
             **({"shift": jax.ShapeDtypeStruct((l.N,), jnp.int32)}
                if int5 else {}))
        for l in cfg.layers]}
    requant = [(jnp.full((l.N,), 16384, jnp.int32),
                jnp.full((l.N,), 20, jnp.int32)) for l in cfg.layers[:-1]]
    imgs = jax.ShapeDtypeStruct((args.batch, H, W, cfg.layers[0].M),
                                jnp.uint8)
    mplan = plan_model(cfg, policy)

    def infer(qp, u8):
        if int5:
            return mplan.forward_int5(qp, u8, requant=requant)
        return cnn_forward_int8(qp, u8, cfg, requant=requant, policy=policy)

    rep = jax.tree.map(lambda _: NamedSharding(mesh, P()), qshapes)
    ish = NamedSharding(mesh, P(dp))
    t0 = time.time()
    with activate_mesh(mesh), mesh:
        compiled = jax.jit(infer, in_shardings=(rep, ish)).lower(
            qshapes, imgs).compile()
    hlo = compiled.as_text()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = total_collective_bytes(hlo)
    conv_flops = sum(layer_ops(l) for l in cfg.layers) * args.batch
    times = {"compute": flops / PEAK_FLOPS_BF16, "memory": byts / HBM_BW,
             "collective": coll / ICI_BW}
    return {
        "arch": cfg.name, "shape": f"{datapath}_infer_{H}x{W}_b{args.batch}",
        "kind": f"{datapath}_infer", "chips": mesh.size,
        "multi_pod": args.multi_pod,
        "mesh": {ax: int(mesh.shape[ax]) for ax in mesh.axis_names},
        "plan": list((mplan.int5 if int5 else mplan.int8).describe()),
        "compile_s": round(time.time() - t0, 1),
        "memory": hbm_bytes_estimate(compiled.memory_analysis()),
        "cost": {"flops": flops, "bytes accessed": byts},
        "collectives": collective_stats(hlo),
        "collective_bytes": coll,
        "roofline": {
            "compute_s": times["compute"],
            "memory_s": times["memory"],
            "collective_s": times["collective"],
            "dominant": max(times, key=times.get),
            "model_flops_total": conv_flops,
            "useful_flops_ratio": (conv_flops / mesh.size) / flops
            if flops else 0.0,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser(parents=[execution_parent(
        arch_choices=CNN_REGISTRY, arch_default="vgg16")])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    policy = policy_from_args(args)
    cfg = CNN_REGISTRY[args.arch]
    mesh = scaled_mesh(args.multi_pod)
    chips = mesh.size

    def train_step(state, batch):
        params, opt = state
        (loss, mets), g = jax.value_and_grad(
            lambda p: cnn_loss(p, batch, cfg, policy=policy),
            has_aux=True)(params)
        params, opt, _ = adamw_update(g, opt, params, 1e-3, AdamWConfig())
        return (params, opt), loss

    pshapes = jax.eval_shape(lambda k: init_cnn(k, cfg),
                             jax.random.PRNGKey(0))
    oshapes = jax.eval_shape(adamw_init, pshapes)
    H, W = cfg.input_hw
    batch = {
        "images": jax.ShapeDtypeStruct(
            (args.batch, H, W, cfg.layers[0].M), jnp.float32),
        "labels": jax.ShapeDtypeStruct((args.batch,), jnp.int32)}

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    rep = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                       (pshapes, oshapes))
    bsh = {"images": NamedSharding(mesh, P(dp)),
           "labels": NamedSharding(mesh, P(dp))}

    t0 = time.time()
    with activate_mesh(mesh), mesh:
        compiled = jax.jit(train_step, in_shardings=(rep, bsh),
                           out_shardings=(rep, None)).lower(
            (pshapes, oshapes), batch).compile()
    hlo = compiled.as_text()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = total_collective_bytes(hlo)
    conv_flops = 3 * sum(layer_ops(l) for l in cfg.layers) * args.batch
    rec = {
        "arch": args.arch, "shape": f"train_{H}x{W}_b{args.batch}",
        "kind": "train", "chips": chips, "emulate_hw": args.emulate_hw,
        "mesh": {ax: int(mesh.shape[ax]) for ax in mesh.axis_names},
        "plan": list(plan_model(cfg, policy).describe()),
        "compile_s": round(time.time() - t0, 1),
        "memory": hbm_bytes_estimate(compiled.memory_analysis()),
        "cost": {"flops": flops, "bytes accessed": byts},
        "collectives": collective_stats(hlo),
        "collective_bytes": coll,
        "roofline": {
            "compute_s": flops / PEAK_FLOPS_BF16,
            "memory_s": byts / HBM_BW,
            "collective_s": coll / ICI_BW,
            "dominant": max(
                (("compute", flops / PEAK_FLOPS_BF16),
                 ("memory", byts / HBM_BW),
                 ("collective", coll / ICI_BW)), key=lambda kv: kv[1])[0],
            "model_flops_total": conv_flops,
            "useful_flops_ratio": (conv_flops / chips) / flops
            if flops else 0.0,
        },
    }
    os.makedirs(args.out, exist_ok=True)
    tag = (f"{args.arch}__cnn_train__"
           f"{'multi' if args.multi_pod else 'single'}"
           f"{'__emuhw' if args.emulate_hw else ''}")
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    r = rec["roofline"]
    print(f"[dryrun_cnn] {tag}: compile {rec['compile_s']}s  "
          f"compute {r['compute_s']*1e3:.1f}ms  memory "
          f"{r['memory_s']*1e3:.1f}ms  collective "
          f"{r['collective_s']*1e3:.1f}ms  useful "
          f"{r['useful_flops_ratio']:.2f}")

    lanes = ([("int8", args.int8)]
             + [("int5", getattr(args, "int5", False))])
    for datapath, wanted in lanes:
        if not wanted:
            continue
        irec = _int_record(cfg, args, mesh, dp, policy, datapath)
        itag = (f"{args.arch}__cnn_{datapath}__"
                f"{'multi' if args.multi_pod else 'single'}")
        with open(os.path.join(args.out, itag + ".json"), "w") as f:
            json.dump(irec, f, indent=1)
        ir = irec["roofline"]
        print(f"[dryrun_cnn] {itag}: compile {irec['compile_s']}s  "
              f"compute {ir['compute_s']*1e3:.1f}ms  memory "
              f"{ir['memory_s']*1e3:.1f}ms  collective "
              f"{ir['collective_s']*1e3:.1f}ms")


if __name__ == "__main__":
    main()
