"""Host spans (repro.obs): phase timing on an injected clock, and the
serving flush worker's and the train loop's spans in a profiler trace."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import CNN_SMOKES
from repro.distributed import TrainLoopConfig, train_loop
from repro.engine import ExecutionPolicy, plan_model
from repro.obs import Laps
from repro.serve import ServeConfig, Server


class TickClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def test_laps_share_boundaries_and_skip_what_no_phase_holds():
    clk = TickClock()
    laps = Laps("serve", clk, batch=0)
    assert laps.t == 1.0
    with laps("pad"):
        clk()                    # work inside the phase
    with laps("stage"):
        pass
    laps.restart()               # one tick that belongs to no phase
    with laps("block"):
        pass
    with pytest.raises(RuntimeError):
        with laps("deliver"):
            raise RuntimeError("a phase that raises adds nothing")
    with laps("pad"):
        pass
    assert laps.seconds == {"pad": 3.0, "stage": 1.0, "block": 1.0}


def _host_spans(trace_dir):
    """(name, {stat: value}) of the ``repro.*`` events on host planes."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, {k: str(v) for k, v in ev.stats}))
    return out


class _Feed:
    def batch_at(self, step):
        return jnp.full((4,), float(step))


def test_serving_and_training_spans_reach_the_trace(tmp_path):
    plan = plan_model(CNN_SMOKES["vgg16"], ExecutionPolicy())
    srv = Server.from_plan(plan, plan.init(jax.random.PRNGKey(0)),
                           ServeConfig(buckets=(1, 4), max_delay_ms=1.0))
    h, w = CNN_SMOKES["vgg16"].input_hw
    c = CNN_SMOKES["vgg16"].layers[0].M
    images = np.random.default_rng(0).normal(size=(5, h, w, c)).astype(np.float32)
    step_fn = jax.jit(lambda s, b: (s + b.sum(), {"loss": b.mean()}))
    step_fn(jnp.float32(0), _Feed().batch_at(0))          # compile outside

    with jax.profiler.trace(str(tmp_path)):
        reqs = [srv.submit(im) for im in images]
        for r in reqs:
            assert r.done.wait(60.0) and r.status == "served"
        srv.close()
        out = train_loop(step_fn, jnp.float32(0), _Feed(),
                         TrainLoopConfig(total_steps=3, ckpt_dir=None),
                         log_fn=lambda *a: None)
    assert len(out["history"]) == 3

    spans = _host_spans(str(tmp_path))
    names = {n for n, _ in spans}
    for phase in ("wait", "pad", "stage", "launch", "block", "deliver"):
        assert f"repro.serve.{phase}" in names
    for phase in ("feed", "dispatch", "sync"):
        steps = sorted(int(m["step"]) for n, m in spans
                       if n == f"repro.train.{phase}")
        assert steps == [0, 1, 2]
    # every batch's staging spans carry its number and bucket, and the
    # numbers are the requests' own
    pads = [m for n, m in spans if n == "repro.serve.pad"]
    assert {int(m["batch"]) for m in pads} == {r.batch for r in reqs}
    for m in pads:
        got = [r for r in reqs if r.batch == int(m["batch"])]
        assert int(m["bucket"]) == srv.batcher.bucket_for(len(got))
    assert all("batch" in m for n, m in spans if n == "repro.serve.wait")
