"""What the serving traffic drivers share: building the program's server
from the cell's launcher arguments, the image pool, warming the buckets,
the serving counters of the window, and the comparison with the plain
reference that the configuration names (``Spec.reference``).

The window drives ``Server.submit`` on a server built by
``launch.serve_cnn.build_server``.
"""

from __future__ import annotations

import gc
import math
from typing import Dict, List

import numpy as np

from bench.harness import RunFailed, log

#: Requests the reference runs at once.
REF_BLOCK = 16


def program_config(run):
    """The program's registry entry for the cell's configuration,
    checked by the configuration's reference module against the
    configuration file; a mismatch fails the run."""
    import repro.configs

    cfg = run.cfg
    registry = getattr(repro.configs, cfg.get("registry", "CNN_REGISTRY"))
    pcfg = registry[cfg["program_arch"]]
    try:
        run.spec.reference(cfg["reference"]).check_program(cfg, pcfg)
    except ValueError as e:
        raise RunFailed(f"configs/{cfg['name']}.json is not the program's {cfg['program_arch']}: {e}") from None
    return pcfg


def require_substrate(run, plan) -> None:
    """Every conv layer has to run on the cell's kernel substrate."""
    want = run.cell.get("substrate", "pallas")
    subs = sorted({d["substrate"] for d in plan.describe()})
    if subs != [want]:
        raise RunFailed(f"conv layers on {subs}, the cell needs {want} on every layer")


def make_images(run, n: int) -> np.ndarray:
    """``n`` distinct float32 images from the run's seed, made on the
    device in one call and copied by numpy into ordinary host memory,
    where a server finds its uploads: the host array that JAX returns for
    a TPU buffer reads at a fifth of that rate on a v5e host."""
    import jax
    import jax.numpy as jnp

    h, w = run.cfg["input_hw"]
    c = run.cfg["in_channels"]
    key = jax.random.fold_in(jax.random.PRNGKey(run.program_seed), 1)
    make = jax.jit(lambda k: jax.random.normal(k, (n, h, w, c), jnp.float32))
    return np.asarray(make(key)).copy()


class ServingDriver:
    """Set-up, release and check for the serving drivers; a subclass
    supplies ``window``."""

    def __init__(self, run):
        self.run = run
        self.server = None

    # -- set-up -----------------------------------------------------------

    def setup(self) -> None:
        from repro.launch import serve_cnn
        from repro.launch.cli import policy_from_args, serve_config_from_args

        run = self.run
        pcfg = program_config(run)
        argv = list(run.cell["argv"]) + ["--seed", str(run.program_seed)]
        args = serve_cnn.build_parser().parse_args(argv)
        sconf = serve_config_from_args(args)
        self.server = serve_cnn.build_server(
            pcfg, policy_from_args(args), sconf, seed=args.seed
        )
        require_substrate(run, self.server.engine.plan)
        self.buckets = tuple(sconf.buckets)
        self.images = make_images(run, int(run.mix["image_pool"]))
        self.warm()
        self.snap0 = self.server.metrics.snapshot()

    def warm(self) -> None:
        """Send one full batch of every bucket through the server, twice,
        so the first window batches find every path warm."""
        for _ in range(2):
            for b in sorted(self.buckets, reverse=True):
                reqs = [self.server.submit(self.images[i % len(self.images)]) for i in range(b)]
                for r in reqs:
                    if not r.done.wait(60.0) or r.status != "served":
                        raise RunFailed(f"warm-up request in bucket {b}: {r.status}")

    def compile_counts(self):
        from repro.engine import execute

        return (
            dict(self.server.engine.compile_counts),
            sum(execute.EXECUTABLE_COMPILES.values()),
        )

    # -- the window's bookkeeping ----------------------------------------

    def finish_window(self) -> None:
        """Close the server (every queued request drained) and keep the
        window's flushes per bucket, which the roofline readers count."""
        self.server.close()
        snap1 = self.server.metrics.snapshot()
        self.run.obs["flushes"] = {
            int(b): rec["flushes"] - self.snap0["per_bucket"].get(b, {"flushes": 0})["flushes"]
            for b, rec in snap1["per_bucket"].items()
        }

    # -- release and check -----------------------------------------------

    def release(self) -> None:
        self.server = None
        gc.collect()

    def sample(self, candidates: List[int]) -> List[int]:
        """The requests to compare, drawn from the seed among the served."""
        n = min(int(self.run.cell["check"]["sample"]), len(candidates))
        rng = np.random.default_rng(self.run.seed)
        return sorted(rng.choice(candidates, size=n, replace=False).tolist())

    def control(self) -> Dict[str, Dict[str, float]]:
        """What the check reads when the reference answers in the
        program's place in a lower precision, on the same sampled
        requests."""
        picked = self.sample(sorted(self.results))
        images = self.images[[self.image_of[i] for i in picked]]
        return {
            lane: self.readings(dict(zip(picked, reference_logits(self.run, images, lane))))
            for lane in ("bf16", "int8")
        }

    def readings(self, results: Dict[int, np.ndarray]) -> Dict[str, float]:
        """Per sampled request, max |served - reference| over the
        reference's largest |logit|: the widest over the sample, and the
        median."""
        ids = sorted(results)
        want = reference_logits(self.run, self.images[[self.image_of[i] for i in ids]])
        got = np.stack([results[i] for i in ids]).astype(np.float64)
        gap = np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)
        gap = np.where(np.isfinite(gap), gap, math.inf)
        log(f"compared {len(ids)} served requests with the reference")
        return {"logit_err": float(gap.max()), "logit_err_median": float(np.median(gap))}

    def read(self) -> Dict[str, float]:
        """The numbers the check compares, on the sampled requests."""
        picked = self.sample(sorted(self.results))
        return self.readings({i: self.results[i] for i in picked})


def reference_logits(run, images: np.ndarray, lane: str = "f32") -> np.ndarray:
    """The plain reference's logits for ``images``, in blocks, with
    weights drawn again from the seed; ``lane`` below ``"f32"`` is a
    lower-precision control."""
    import jax

    reference = run.spec.reference(run.cfg["reference"])
    params = jax.jit(lambda: reference.init_params(run.cfg, run.program_seed))()
    fwd = jax.jit(lambda p, x: reference.forward(run.cfg, p, x, lane))
    return np.concatenate(
        [np.asarray(fwd(params, images[k : k + REF_BLOCK]), np.float64) for k in range(0, len(images), REF_BLOCK)]
    )
