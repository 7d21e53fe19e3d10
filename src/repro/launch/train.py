"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \
      --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real TPU slice this would run under `jax.distributed.initialize()`
with the production mesh; in this container it runs the smoke config on
the host devices (the full configs are exercised by the dry-run).

CNN archs (vgg16 / alexnet — the paper's own workloads) train through the
TrIM conv path in BOTH directions: the fused forward Pallas kernel and its
custom VJP (input-grad / weight-grad kernel pair, DESIGN.md §6).

  PYTHONPATH=src python -m repro.launch.train --arch vgg16 --smoke \
      --steps 3 --batch 4 --substrate pallas

``--substrate pallas`` (or the deprecated ``--force-pallas`` alias) runs
the Pallas kernels off-TPU in interpret mode — CI's train-smoke lane uses
it to prove the backward path on CPU runners; the launcher exits non-zero
unless the loss AND grad_norm of every step are finite, so backward-path
regressions fail PRs.  ``--int8`` additionally quantizes the trained conv
stack and runs the fused-requant integer datapath once through the same
execution plan.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CNN_REGISTRY, CNN_SMOKES, get_config, get_smoke
from repro.data import SyntheticImageDataset, SyntheticLMDataset
from repro.distributed import (StepConfig, TrainLoopConfig, activate_mesh,
                               make_train_state, make_train_step, state_pspec,
                               train_loop)
from repro.distributed.steps import _to_shardings, batch_pspec
from repro.launch.cache import enable_compile_cache
from repro.launch.cli import execution_parent, policy_from_args
from repro.launch.mesh import make_host_mesh
from repro.nn.models import build_model


def _int8_check(model, params, batch) -> None:
    """Quantize + calibrate + run the fused int8 inference datapath once
    (plan entry points), printing the output stats."""
    qp, _ = model.quantize(params)
    imgs = np.asarray(batch["images"])
    lo, hi = float(imgs.min()), float(imgs.max())
    u8 = jnp.asarray(np.clip((imgs - lo) / max(hi - lo, 1e-6) * 255,
                             0, 255).astype(np.uint8))
    pairs = model.calibrate_requant(qp, u8)
    feat = model.forward_int8(qp, u8, requant=pairs)
    finite = bool(np.isfinite(np.asarray(feat, np.float64)).all())
    print(f"[train] int8 datapath: output {feat.shape} dtype {feat.dtype} "
          f"finite={finite} (fused per-channel requant)")
    if not finite:
        raise SystemExit("[train] FAIL: non-finite int8 feature map")


def _int5_check(model, params, batch) -> None:
    """Quantize to the MSR-compressed int5 lane (DESIGN.md §9.3), calibrate
    the exponent-folded requant pairs, and run the fused datapath once."""
    qp, _ = model.quantize_int5(params)
    imgs = np.asarray(batch["images"])
    lo, hi = float(imgs.min()), float(imgs.max())
    u8 = jnp.asarray(np.clip((imgs - lo) / max(hi - lo, 1e-6) * 255,
                             0, 255).astype(np.uint8))
    pairs = model.calibrate_requant_int5(qp, u8)
    feat = model.forward_int5(qp, u8, requant=pairs)
    finite = bool(np.isfinite(np.asarray(feat, np.float64)).all())
    print(f"[train] int5 datapath: output {feat.shape} dtype {feat.dtype} "
          f"finite={finite} (MSR weights, exponent-folded requant)")
    if not finite:
        raise SystemExit("[train] FAIL: non-finite int5 feature map")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(parents=[execution_parent(
        arch_required=True)])
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis size of the host mesh")
    return ap


def step_config(args) -> StepConfig:
    return StepConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                      total_steps=args.steps, accum=args.accum,
                      compress_grads=args.compress_grads)


def cnn_data(cfg, batch: int):
    """The synthetic image dataset a CNN arch trains on, and its batch
    shapes."""
    H, W = cfg.input_hw
    c_in = cfg.layers[0].M
    ds = SyntheticImageDataset(hw=cfg.input_hw, channels=c_in,
                               n_classes=cfg.n_classes, global_batch=batch)
    shapes = {
        "images": jax.ShapeDtypeStruct((batch, H, W, c_in), jnp.float32),
        "labels": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    return ds, shapes


def sharded_train_step(model, scfg, mesh, ctx, state, batch_shapes):
    """Place ``state`` on ``mesh`` and jit the train step with the state
    and batch shardings (state donated).  Call inside
    ``activate_mesh(mesh)``: the step traces under that context.
    Returns (placed state, step, state shardings)."""
    sshard = _to_shardings(state_pspec(state, ctx), mesh)
    state = jax.device_put(state, sshard)
    step = jax.jit(make_train_step(model, scfg, mesh),
                   in_shardings=(sshard, _to_shardings(
                       batch_pspec(batch_shapes, ctx), mesh)),
                   out_shardings=(sshard, None),
                   donate_argnums=(0,))
    return state, step, sshard


def main(argv=None) -> None:
    enable_compile_cache()
    args = build_parser().parse_args(argv)

    policy = policy_from_args(args)
    is_cnn = args.arch in CNN_REGISTRY
    if is_cnn:
        cfg = CNN_SMOKES[args.arch] if args.smoke else CNN_REGISTRY[args.arch]
        ds, batch_shapes = cnn_data(cfg, args.batch)
    else:
        cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
        ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq + 1,
                                global_batch=args.batch)
        batch_shapes = {
            "tokens": jax.ShapeDtypeStruct((args.batch, args.seq + 1),
                                           jnp.int32)}

    mesh = make_host_mesh(model=args.tp)
    model = build_model(cfg, tp=int(mesh.shape["model"]),
                        policy=policy if is_cnn else None)
    scfg = step_config(args)

    with activate_mesh(mesh) as ctx, mesh:
        state, step, sshard = sharded_train_step(
            model, scfg, mesh, ctx, make_train_state(
                model, jax.random.PRNGKey(0)), batch_shapes)
        out = train_loop(step, state, ds,
                         TrainLoopConfig(total_steps=args.steps,
                                         ckpt_every=args.ckpt_every,
                                         ckpt_dir=args.ckpt_dir),
                         state_shardings=sshard)
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    grad_norm = hist[-1].get("grad_norm", float("nan"))
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"grad_norm {grad_norm:.4f}; "
          f"{len(out['stragglers'])} straggler steps")
    # Backward-path health gate (CI train-smoke lane): a broken VJP shows
    # up as NaN/Inf loss or grad_norm — fail loudly, not silently.  Every
    # step is checked (skip_nonfinite keeps the *state* sane on a bad
    # step, which would otherwise mask a batch-dependent NaN from a
    # final-step-only check).
    bad = [h["step"] for h in hist
           if not (np.isfinite(h["loss"])
                   and np.isfinite(h.get("grad_norm", float("nan"))))]
    if bad:
        raise SystemExit(f"[train] FAIL: non-finite loss or grad_norm at "
                         f"steps {bad} — backward path broken")
    if args.int8:
        if not is_cnn:
            print("[train] --int8 ignored: LM arch has no int8 conv path")
        else:
            b = ds.batch_at(0)
            _int8_check(model, out["state"]["params"],
                        {"images": jnp.asarray(b["images"])})
    if getattr(args, "int5", False):
        if not is_cnn:
            print("[train] --int5 ignored: LM arch has no int5 conv path")
        else:
            b = ds.batch_at(0)
            _int5_check(model, out["state"]["params"],
                        {"images": jnp.asarray(b["images"])})


if __name__ == "__main__":
    main()
