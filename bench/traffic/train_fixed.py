"""Training at a fixed global batch, a few distinct batches cycled.

Set-up builds one object, the compiled step with its state
(``launch.train.sharded_train_step`` from the cell's launcher
arguments), and drives it through its first three steps with
``distributed.train_loop``, the window's own call and feed, on batches
whose rows all differ.  The window goes on with the same object, in
chunks of steps, until ``--seconds`` have passed; the last step of each
chunk is blocked on.  The reference that the configuration names
(``Spec.reference``) follows the first three steps.

The mesh is the cell's own chips as (chips, 1) data x model: each chip
holds the whole model and its share of every batch, and the gradients
are summed across the chips in each step.
"""

from __future__ import annotations

import contextlib
import gc
import math
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench.harness import RunFailed, log
from bench.reference import adamw
from bench.serving import program_config, require_substrate
from bench.work import train_flops

#: Steps the reference follows.
CHECKED_STEPS = 3
#: A leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone and is left out of the change.
STILL_LEAF = 1e-3


class _Feed:
    """The window's feed: the set-up's batches, cycled."""

    def __init__(self, batches):
        self.batches = batches
        self.next = 0

    def batch_at(self, i: int):
        return self.batches[(self.next + i) % len(self.batches)]


def leaf_norms(tree) -> List[float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)])
    return [float(x) for x in norms(tree)]


def leaf_gaps(got: List[float], want: List[float], keep=None) -> List[float]:
    """Per leaf, |program norm - reference norm| over the larger of that
    leaf's reference norm and the median leaf's."""
    idx = [i for i in range(len(want)) if keep is None or keep[i]]
    med = float(np.median([want[i] for i in idx]))
    return [abs(got[i] - want[i]) / max(want[i], med) for i in idx]


def check_optimizer(opt: Dict[str, Any], scfg) -> None:
    """The program's step has to run the optimizer the cell states."""
    have = {
        "peak_lr": scfg.peak_lr,
        "warmup_steps": scfg.warmup_steps,
        "total_steps": scfg.total_steps,
        "b1": scfg.adamw.b1,
        "b2": scfg.adamw.b2,
        "eps": scfg.adamw.eps,
        "weight_decay": scfg.adamw.weight_decay,
        "clip_norm": scfg.adamw.clip_norm,
        "accum": scfg.accum,
    }
    for key, value in have.items():
        if not math.isclose(float(opt[key]), float(value), rel_tol=1e-12):
            raise RunFailed(f"the program's optimizer has {key}={value}, the cell states {opt[key]}")


class Driver:
    def __init__(self, run):
        self.run = run
        self.stack = contextlib.ExitStack()

    # -- set-up -----------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType

        from repro.distributed import activate_mesh, make_train_state
        from repro.distributed.steps import _to_shardings, batch_pspec
        from repro.launch import train
        from repro.launch.cli import policy_from_args
        from repro.nn.models import build_model

        run = self.run
        pcfg = program_config(run)
        gb = int(run.mix["global_batch"])
        args = train.build_parser().parse_args(list(run.cell["argv"]) + ["--batch", str(gb)])
        scfg = train.step_config(args)
        check_optimizer(run.cell["optimizer"], scfg)
        model = build_model(pcfg, tp=1, policy=policy_from_args(args))
        require_substrate(run, model.plan)
        _, shapes = train.cnn_data(pcfg, gb)
        mesh = jax.make_mesh(
            (len(run.devices), 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2, devices=run.devices
        )
        ctx = self.stack.enter_context(activate_mesh(mesh))
        self.stack.enter_context(mesh)

        key = jax.random.PRNGKey(run.program_seed)
        state = jax.jit(lambda k: make_train_state(model, k))(key)
        h, w = run.cfg["input_hw"]
        nb = int(run.mix["batches"])
        make = jax.jit(
            lambda k: (
                jax.random.normal(jax.random.fold_in(k, 1), (nb, gb, h, w, run.cfg["in_channels"]), jnp.float32),
                jax.random.randint(jax.random.fold_in(k, 2), (nb, gb), 0, run.cfg["n_classes"], jnp.int32),
            )
        )
        images, labels = make(key)
        bshard = _to_shardings(batch_pspec(shapes, ctx), mesh)
        self.batches = [
            jax.device_put({"images": images[i], "labels": labels[i]}, bshard) for i in range(nb)
        ]
        del images, labels
        state, self.step, self.sshard = train.sharded_train_step(model, scfg, mesh, ctx, state, shapes)
        self.p0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(state["params"])
        self.feed = _Feed(self.batches)
        self.state = state
        self.history: List[Dict[str, float]] = []

        first = self.chunk(1)
        b1 = float(run.cell["optimizer"]["b1"])
        g1 = jax.jit(lambda m: jax.tree.map(lambda x: x / (1.0 - b1), m))(self.state["opt"]["m"])
        self.g1 = jax.device_put(g1, run.devices[0])
        self.chunk(CHECKED_STEPS - 1)
        self.delta = leaf_norms(jax.tree.map(lambda a, b: a - b, self.state["params"], self.p0))
        self.p0 = None
        self.losses = [h["loss"] for h in self.history[:CHECKED_STEPS]]
        log(f"first {CHECKED_STEPS} losses {self.losses}, step 1 took {first:.3f} s")

    def chunk(self, n: int) -> float:
        """``n`` steps through ``train_loop``; returns their seconds."""
        from repro.distributed import TrainLoopConfig, train_loop

        t0 = time.perf_counter()
        out = train_loop(
            self.step,
            self.state,
            self.feed,
            TrainLoopConfig(total_steps=n, ckpt_dir=None),
            state_shardings=self.sshard,
            log_fn=lambda *a: None,
        )
        dt = time.perf_counter() - t0
        self.state = out["state"]
        self.feed.next += n
        self.history.extend(out["history"])
        return dt

    def compile_counts(self):
        return self.step._cache_size()

    # -- the window -------------------------------------------------------

    def window(self, seconds: float) -> None:
        run = self.run
        n = int(run.mix["chunk"])
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < seconds:
            self.chunk(n)
            steps += n
        elapsed = time.perf_counter() - t0
        gb = int(run.mix["global_batch"])
        run.e2e["train_step_s"] = elapsed / steps
        run.obs["steps"] = steps
        run.obs["train_flops"] = train_flops(run.cfg) * gb
        run.obs["global_batch"] = gb
        self.attempted = steps
        self.failed = sum(
            1
            for h in self.history
            if not (math.isfinite(h["loss"]) and math.isfinite(h.get("grad_norm", math.nan)))
            or h.get("skipped", 0.0)
        )

    # -- release and check -----------------------------------------------

    def release(self) -> None:
        self.state = self.step = None
        self.stack.close()
        gc.collect()

    def read(self) -> Dict[str, float]:
        """The numbers the check compares, against the reference's first
        steps."""
        self.ref = reference_steps(self.run, self.batches)
        log(f"reference losses {self.ref['losses']}")
        return self.readings({"losses": self.losses, "g1": self.g1, "delta": self.delta})

    def readings(self, got: Dict[str, Any]) -> Dict[str, float]:
        """Losses, the first gradient and the change after the checked
        steps, against the reference's: each step's loss; per leaf the
        gap of norms, by the worst leaf and by the median one; per leaf
        the norm of the first gradient's difference, over the same
        denominator; and that difference on the output layer's bias, over
        its reference norm.  That bias's gradient is the batch mean of
        softmax minus one-hot, with no product after the logits: every
        row's label shows in it whole, and the products' rounding only
        through the logits."""
        import jax

        ref = self.ref
        want_g1 = leaf_norms(ref["g1"])
        keep = [g >= STILL_LEAF * float(np.median(want_g1)) for g in want_g1]
        losses = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
        g1 = leaf_gaps(leaf_norms(got["g1"]), want_g1)
        d3 = leaf_gaps(got["delta"], ref["delta"], keep)
        gdiff = jax.tree.map(lambda a, b: a - b, got["g1"], ref["g1"])
        diff = leaf_norms(gdiff)
        med = float(np.median(want_g1))
        g1_err = [d / max(w, med) for d, w in zip(diff, want_g1)]
        out_bias = leaf_norms(gdiff["fc"][-1]["bias"])[0] / leaf_norms(ref["g1"]["fc"][-1]["bias"])[0]
        log(f"worst leaves: grad1 {int(np.argmax(g1))}, delta3 {int(np.argmax(d3))}, grad1 difference {int(np.argmax(g1_err))}")
        return {
            "loss1_gap": losses[0],
            "loss_gap": max(losses),
            "grad1_gap": max(g1),
            "grad1_median_gap": float(np.median(g1)),
            "grad1_err": max(g1_err),
            "grad1_median_err": float(np.median(g1_err)),
            "out_bias_grad1_err": out_bias,
            "delta3_gap": max(d3),
            "delta3_median_gap": float(np.median(d3)),
        }

    def control(self) -> Dict[str, Dict[str, float]]:
        """What the check reads with the reference in the program's place
        in int8 (the control), and with planted faults: half of each
        batch left out and, on several chips, the exchange between them
        left out (the first chip's update from its own rows alone); call
        after ``read``."""
        gb = int(self.run.mix["global_batch"])
        out = {
            "int8": self.readings(reference_steps(self.run, self.batches, lane="int8")),
            "half_batch": self.readings(reference_steps(self.run, self.batches, rows=gb // 2)),
        }
        chips = len(self.run.devices)
        if chips > 1:
            out["no_exchange"] = self.readings(reference_steps(self.run, self.batches, rows=gb // chips))
        return out


def row_blocks(batch, rows: int, block: int) -> List[Tuple[Any, Any, Any]]:
    """The batch's first ``rows`` rows in blocks of at most ``block``,
    each ``(device, images, labels)`` on the chip that holds those rows,
    in row order."""
    labels = {s.device: s.data for s in batch["labels"].addressable_shards}
    shards = {s.index[0].start or 0: s for s in batch["images"].addressable_shards}
    out = []
    for lo in sorted(shards):
        x, y = shards[lo].data, labels[shards[lo].device]
        n = min(x.shape[0], rows - lo)
        for k in range(0, n, block):
            out.append((shards[lo].device, x[k : min(k + block, n)], y[k : min(k + block, n)]))
    return out


def reference_steps(run, batches, lane: str = "f32", rows=None) -> Dict[str, Any]:
    """The plain reference's first steps from the seed: each step's loss,
    the first (clipped) gradient, and the leaf norms of the change of the
    parameters after the last step.  ``lane`` below ``"f32"``
    computes the passes in a lower precision (a control); ``rows`` takes
    only that many rows of each batch (a planted fault).  Each block of
    rows runs on the chip that holds it; the chips' sums meet on the
    first chip, which updates the parameters."""
    import jax
    import jax.numpy as jnp

    reference = run.spec.reference(run.cfg["reference"])
    opt = run.cell["optimizer"]
    cfg = run.cfg
    block = int(run.cell["check"]["block"])
    home = run.devices[0]
    params = jax.jit(lambda: reference.init_params(cfg, run.program_seed))()
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(lambda p, x, y: reference.loss(cfg, p, x, y, lane)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    step_fn = jax.jit(
        lambda p, g, m, v, s, lr: adamw.update(opt, p, adamw.clip(opt, g), m, v, s, lr),
        static_argnums=(4,),
    )
    losses, g1 = [], None
    for s in range(CHECKED_STEPS):
        b = batches[s % len(batches)]
        blocks = row_blocks(b, rows or b["labels"].shape[0], block)
        n = sum(x.shape[0] for _, x, _ in blocks)
        placed = {dev: jax.device_put(params, dev) for dev, _, _ in blocks}
        parts, sums = [], {}
        for dev, x, y in blocks:
            lval, g = grad(placed[dev], x, y)
            w = x.shape[0] / n
            g = jax.tree.map(lambda t: t * w, g)
            parts.append((lval, w))
            sums[dev] = add(sums[dev], g) if dev in sums else g
        tot_l, tot_g = 0.0, None
        for lval, w in parts:
            tot_l += float(lval) * w
        for g in sums.values():
            g = jax.device_put(g, home)
            tot_g = g if tot_g is None else add(tot_g, g)
        if s == 0:
            g1 = jax.jit(lambda g: adamw.clip(opt, g))(tot_g)
        params, m, v = step_fn(params, tot_g, m, v, s, adamw.learning_rate(opt, s))
        losses.append(tot_l)
    delta = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses, "g1": g1, "delta": delta}
