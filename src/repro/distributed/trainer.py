"""Fault-tolerant training loop.

Features (DESIGN.md §6):
- async sharded checkpoints every `ckpt_every` steps, atomic commit;
- auto-resume from the latest *committed* step (torn checkpoints skipped);
- elastic restore: the checkpoint is mesh-agnostic; restoring under a
  different mesh re-shards via the current PartitionSpecs;
- NaN/Inf step skip (inside the jitted step — the state update is gated);
- straggler/flake detection: per-step wall time EWMA + z-score flagging,
  with the slow-step log returned to the caller;
- deterministic data: the pipeline is a pure function of (seed, step), so
  resume at step k replays exactly the batches steps k, k+1, ... would
  have seen;
- host spans (``repro.obs``), each tagged ``step=<n>``: ``train.feed``
  (``dataset.batch_at``), ``train.dispatch`` (the ``step_fn`` call),
  ``train.sync`` (``block_until_ready`` and the scalar metrics' reads)
  and ``train.ckpt`` (a checkpoint save, tagged with the checkpoint's
  step); a step's ``dt_s`` is its dispatch and sync.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.obs import Laps, span


@dataclass
class StragglerMonitor:
    """EWMA wall-time tracker; flags steps slower than mean + z * std."""
    alpha: float = 0.1
    z_threshold: float = 3.0
    mean: float = 0.0
    var: float = 0.0
    n: int = 0
    flagged: List[Dict[str, float]] = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        if self.n >= 5:   # warmup
            std = max(self.var ** 0.5, 1e-6)
            if dt > self.mean + self.z_threshold * std:
                self.flagged.append({"step": step, "dt": dt,
                                     "mean": self.mean, "std": std})
                # do not poison the EWMA with the outlier
                self.n += 1
                return True
        delta = dt - self.mean
        self.mean += self.alpha * delta if self.n else delta
        self.var = (1 - self.alpha) * (self.var + self.alpha * delta ** 2) \
            if self.n else 0.0
        self.n += 1
        return False


@dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_last: int = 3
    log_every: int = 10
    resume: bool = True


def train_loop(step_fn: Callable, state, dataset, loop_cfg: TrainLoopConfig,
               state_shardings=None, log_fn: Callable = print,
               ) -> Dict[str, Any]:
    """Run the loop; returns {state, history, stragglers, resumed_from}."""
    mgr = (CheckpointManager(loop_cfg.ckpt_dir, loop_cfg.keep_last)
           if loop_cfg.ckpt_dir else None)
    start = 0
    resumed_from = None
    if mgr is not None and loop_cfg.resume:
        step, restored = mgr.restore_latest(state, state_shardings)
        if step is not None:
            state, start, resumed_from = restored, step, step
            log_fn(f"[trainer] resumed from step {step}")

    monitor = StragglerMonitor()
    history: List[Dict[str, float]] = []
    for step in range(start, loop_cfg.total_steps):
        laps = Laps("train", time.perf_counter, step=step)
        with laps("feed"):
            batch = dataset.batch_at(step)
        with laps("dispatch"):
            state, metrics = step_fn(state, batch)
        with laps("sync"):
            jax.block_until_ready(metrics["loss"])
            scalars = {k: float(np.asarray(v)) for k, v in metrics.items()
                       if np.ndim(v) == 0}
        dt = laps.seconds["dispatch"] + laps.seconds["sync"]
        slow = monitor.observe(step, dt)
        row = {"step": step, "dt_s": dt, **scalars}
        history.append(row)
        if slow:
            log_fn(f"[trainer] straggler step {step}: {dt:.3f}s "
                   f"(mean {monitor.mean:.3f}s)")
        if step % loop_cfg.log_every == 0 or step == loop_cfg.total_steps - 1:
            log_fn(f"[trainer] step {step} loss {row.get('loss', float('nan')):.4f} "
                   f"({dt*1e3:.0f} ms)")
        if mgr is not None and (step + 1) % loop_cfg.ckpt_every == 0:
            with span("train.ckpt", step=step + 1):
                mgr.save(state, step + 1)
    if mgr is not None:
        with span("train.ckpt", step=loop_cfg.total_steps):
            mgr.save(state, loop_cfg.total_steps)
            mgr.wait()
    return {"state": state, "history": history,
            "stragglers": monitor.flagged, "resumed_from": resumed_from}
