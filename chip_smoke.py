#!/usr/bin/env python3
"""On-chip smoke test of the main path, at published widths.

  python3 chip_smoke.py              # one TPU chip: phases (b)-(e)
  python3 chip_smoke.py --chips 4    # four chips: data-parallel train step

Drives the launchers' own entry points in this one process (no child
processes), with ``--tuning off`` and the substrate left on ``auto``:

  (a) report the device; exit non-zero unless it is a TPU, and before
      each phase require every conv layer's plan to be on ``pallas``;
  (b) serve VGG-16 (224x224, float) through ``serve_cnn.build_server`` +
      ``Server.run_stream``, buckets 1,8; every request served, none
      failed; logits against the f32 oracle plan at "highest" precision;
  (c) the same on the int8 lane; bit-identical to the f32exact plan;
  (d) serve AlexNet (227x227, float): the strided and grouped layers;
  (e) 3 VGG-16 training steps at batch 8 through ``launch.train``'s
      sharded step (Pallas forward + custom VJP); finite loss and grad
      norm, step 1's loss against the oracle plan's.

``--chips 4`` runs only the data-parallel VGG-16 train step over
``make_host_mesh()`` (a (4, 1) data x model mesh, 2 images per chip) and
the same step on one device, and compares loss and updated params.

Each phase prints its compile (set-up) time and steady time; any failed
check raises, so the script exits non-zero.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Float logits / loss vs the f32 oracle: max |a - b| / max |b|.  XLA runs
#: the model's f32 matmuls (the FC head) at its default TPU precision, one
#: bf16 pass (8 mantissa bits, relative rounding 2**-9 per operand), while
#: the oracle runs at "highest"; through 16 layers that stays well under
#: this bound, and a wrong tap, halo or fold is off by O(1).
FLOAT_RTOL = 2e-2
#: Four chips vs one: the same program, only the batch reduction order
#: differs (f32 sums), so loss agrees to f32 rounding; Adam's first step
#: (update ~ lr * g / |g|) amplifies tiny gradient differences only for
#: near-zero gradient entries, so the update is compared in norm.
DP_LOSS_RTOL = 1e-4
DP_UPDATE_RTOL = 1e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def require_pallas(name: str, plan) -> None:
    subs = {d["substrate"] for d in plan.describe()}
    if subs != {"pallas"}:
        raise SystemExit(f"[chip_smoke] {name}: layer substrates {subs}, "
                         "expected pallas on every layer")


# -- serving (phases b, c, d) ------------------------------------------------

def serve_phase(name: str, argv) -> None:
    import jax
    import numpy as np

    from repro.configs import CNN_REGISTRY
    from repro.engine import ExecutionPolicy, plan_model
    from repro.launch import serve_cnn
    from repro.launch.cli import policy_from_args, serve_config_from_args

    args = serve_cnn.build_parser().parse_args(argv)
    policy = policy_from_args(args)
    serve_config = serve_config_from_args(args)
    cfg = CNN_REGISTRY[args.arch]
    t0 = time.perf_counter()
    server = serve_cnn.build_server(cfg, policy, serve_config, seed=args.seed)
    compile_s = time.perf_counter() - t0
    plan = server.engine.plan
    require_pallas(name, plan.int8 if args.int8 else plan)
    try:
        t0 = time.perf_counter()
        metrics = server.run_stream(
            serve_cnn.make_stream(cfg, args, serve_config.buckets),
            producers=0)
        steady_s = time.perf_counter() - t0
    finally:
        server.close()
    # no fault plan, shedding or timeout: every request must be served
    fails = serve_cnn.check_run(server, metrics, args.requests,
                                expect_all_buckets=True)
    if fails:
        raise SystemExit(f"[chip_smoke] {name}: " + "; ".join(fails))
    tot = metrics.snapshot()["totals"]
    reqs = sorted(metrics.requests, key=lambda r: r.rid)
    imgs = np.stack([r.payload for r in reqs])
    got = np.stack([r.result for r in reqs])
    lane = server.engine.lanes[0]
    if args.int8:
        ref_plan = plan_model(cfg, ExecutionPolicy(substrate="f32exact"))
        ex = ref_plan.executable_for(len(reqs), datapath="int8")
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ex(lane.params, jax.numpy.asarray(imgs),
                                 lane.requant))
        if not np.array_equal(got, want):
            n_bad = int((got != want).sum())
            raise SystemExit(f"[chip_smoke] {name}: {n_bad} of {got.size} "
                             "int8 outputs differ from the f32exact plan")
        check = f"bit-identical to f32exact over {got.size} outputs"
    else:
        ref_plan = plan_model(cfg, ExecutionPolicy(substrate="oracle"))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ref_plan.forward)(lane.params, imgs))
        err = rel_err(got, want)
        if not (np.isfinite(got).all() and err <= FLOAT_RTOL):
            raise SystemExit(f"[chip_smoke] {name}: logits max rel err "
                             f"{err:.3e} > tol {FLOAT_RTOL:.0e}")
        check = f"logits max rel err {err:.3e} (tol {FLOAT_RTOL:.0e})"
    log(f"{name}: compile {compile_s:.1f} s, steady {steady_s:.3f} s for "
        f"{tot['images']} requests; served {tot['images']}/"
        f"{tot['submitted']}, failed {tot.get('failed', 0)}; {check}")


# -- training (phase e, and the four-chip phase) -----------------------------

def train_setup(argv):
    import jax

    from repro.configs import CNN_REGISTRY
    from repro.distributed import make_train_state
    from repro.launch import train
    from repro.launch.cli import policy_from_args
    from repro.nn.models import build_model

    args = train.build_parser().parse_args(argv)
    cfg = CNN_REGISTRY[args.arch]
    model = build_model(cfg, tp=1, policy=policy_from_args(args))
    ds, shapes = train.cnn_data(cfg, args.batch)
    state = make_train_state(model, jax.random.PRNGKey(0))
    return args, cfg, model, ds, shapes, train.step_config(args), state


def train_phase(name: str, argv) -> None:
    import jax
    import numpy as np

    from repro.distributed import TrainLoopConfig, activate_mesh, train_loop
    from repro.engine import ExecutionPolicy, plan_model
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    args, cfg, model, ds, shapes, scfg, state = train_setup(argv)
    require_pallas(name, model.plan)
    ref_plan = plan_model(cfg, ExecutionPolicy(substrate="oracle"))
    with jax.default_matmul_precision("highest"):
        ref_loss, _ = jax.jit(ref_plan.loss)(state["params"], ds.batch_at(0))
    ref_loss = float(ref_loss)
    mesh = make_host_mesh()
    with activate_mesh(mesh) as ctx, mesh:
        state, step, sshard = train.sharded_train_step(
            model, scfg, mesh, ctx, state, shapes)
        out = train_loop(step, state, ds,
                         TrainLoopConfig(total_steps=args.steps,
                                         ckpt_dir=None),
                         state_shardings=sshard, log_fn=log)
    hist = out["history"]
    for h in hist:
        if not (np.isfinite(h["loss"])
                and np.isfinite(h.get("grad_norm", np.nan))):
            raise SystemExit(f"[chip_smoke] {name}: step {h['step']} loss "
                             f"{h['loss']} grad_norm {h['grad_norm']}")
    err = abs(hist[0]["loss"] - ref_loss) / abs(ref_loss)
    if err > FLOAT_RTOL:
        raise SystemExit(f"[chip_smoke] {name}: step-1 loss "
                         f"{hist[0]['loss']:.6f} vs oracle {ref_loss:.6f}: "
                         f"rel err {err:.3e} > tol {FLOAT_RTOL:.0e}")
    steady = [h["dt_s"] for h in hist[1:]]
    log(f"{name}: compile+step1 {hist[0]['dt_s']:.1f} s, steady "
        f"{sum(steady) / len(steady):.3f} s/step; losses "
        f"{[round(h['loss'], 6) for h in hist]}, grad_norms "
        f"{[round(h['grad_norm'], 4) for h in hist]}; step-1 loss vs "
        f"oracle {ref_loss:.6f}: rel err {err:.3e} (tol {FLOAT_RTOL:.0e})")


def train_dp_phase(name: str, argv) -> None:
    """The data-parallel step on a (4, 1) mesh vs the same step on one
    device, from the same state and batch."""
    import jax
    import numpy as np

    from repro.distributed import activate_mesh, make_train_step
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    args, cfg, model, ds, shapes, scfg, state = train_setup(argv)
    require_pallas(name, model.plan)
    batch = ds.batch_at(0)
    def host(t):
        return jax.tree.map(np.asarray, t)

    p0 = host(state["params"])

    t0 = time.perf_counter()
    s1, m1 = jax.jit(make_train_step(model, scfg))(state, batch)
    p1, loss1 = host(s1["params"]), float(m1["loss"])
    t1 = time.perf_counter() - t0

    mesh = make_host_mesh()
    if dict(mesh.shape) != {"data": 4, "model": 1}:
        raise SystemExit(f"[chip_smoke] {name}: mesh {dict(mesh.shape)}")
    t0 = time.perf_counter()
    with activate_mesh(mesh) as ctx, mesh:
        state4, step, _ = train.sharded_train_step(
            model, scfg, mesh, ctx, state, shapes)
        s4, m4 = step(state4, batch)
        loss4 = float(m4["loss"])
    t4 = time.perf_counter() - t0
    # The updated state lives on all four chips, each holding at least a
    # quarter of the params' bytes (ZeRO/FSDP rules may shard them over
    # "data"; replicated, each chip holds all of them).
    leaves = jax.tree_util.tree_leaves(s4["params"])
    per_dev = {d: 0 for d in jax.devices()}
    for x in leaves:
        for sh in x.addressable_shards:
            per_dev[sh.device] += sh.data.nbytes
    quarter = sum(x.nbytes for x in leaves) // 4
    in_use = sorted(per_dev.values())
    if in_use[0] < quarter:
        raise SystemExit(f"[chip_smoke] {name}: param bytes per device "
                         f"{in_use} < a quarter of the params, {quarter}")
    p4 = host(s4["params"])
    loss_err = abs(loss4 - loss1) / abs(loss1)
    num = sum(float(((a - b) ** 2).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(p4), jax.tree_util.tree_leaves(p1)))
    den = sum(float(((b - c) ** 2).sum()) for b, c in zip(
        jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p0)))
    upd_err = (num / den) ** 0.5
    max_diff = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(p4), jax.tree_util.tree_leaves(p1)))
    log(f"{name}: one device {t1:.1f} s, four chips {t4:.1f} s "
        f"(compile + 1 step each); loss {loss4:.6f} vs {loss1:.6f} "
        f"(rel err {loss_err:.3e}, tol {DP_LOSS_RTOL:.0e}); update "
        f"|d4 - d1| / |d1| {upd_err:.3e} (tol {DP_UPDATE_RTOL:.0e}), max "
        f"param diff {max_diff:.3e}; param bytes per device {in_use}")
    if loss_err > DP_LOSS_RTOL or upd_err > DP_UPDATE_RTOL:
        raise SystemExit(f"[chip_smoke] {name}: four-chip step disagrees "
                         "with the one-device step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving + training phases on one chip; 4: the "
                         "data-parallel train step only")
    args = ap.parse_args()
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"[chip_smoke] no src/repro next to {__file__}")
    sys.path.insert(0, src)

    from repro.launch.cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(devs)} devices; compile cache {cache}")
    if dev.platform != "tpu":
        raise SystemExit(f"[chip_smoke] needs a TPU, found {dev.platform}")
    if len(devs) < args.chips:
        raise SystemExit(f"[chip_smoke] --chips {args.chips}: only "
                         f"{len(devs)} devices")

    common = ["--tuning", "off", "--substrate", "auto"]
    serve = common + ["--buckets", "1,8", "--requests", "16"]
    train = common + ["--arch", "vgg16", "--batch", "8", "--steps", "3"]
    if args.chips == 4:
        phases = [(train_dp_phase, "vgg16 data-parallel train", train)]
    else:
        phases = [
            (serve_phase, "(b) vgg16 float serve", serve + ["--arch", "vgg16"]),
            (serve_phase, "(c) vgg16 int8 serve",
             serve + ["--arch", "vgg16", "--int8"]),
            (serve_phase, "(d) alexnet float serve",
             serve + ["--arch", "alexnet"]),
            (train_phase, "(e) vgg16 train", train),
        ]
    for fn, name, argv in phases:
        t0 = time.perf_counter()
        fn(name, argv)
        log(f"{name}: phase wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
