"""The data-parallel training cell on four devices, at a size the CPU
holds: one child process with four CPU devices runs the four-chip test
cell through the harness, sound, with the exchange between chips left
out, and with the reference in the program's place (the int8 control
and the planted faults)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tests.bench.benchroot import BENCH

ROOT = os.path.dirname(BENCH)

CHILD = """
import json, sys
from bench.harness import checks_from
from tests.bench.benchroot import cpu, execute, make_root, make_run

root = make_root(sys.argv[1], sys.argv[2])
out = {"sound": execute(make_run(root, "train4"))}

run = make_run(root, "train4")
cpu(run)
drv = run.spec.driver(run.mix["driver"]).Driver(run)
drv.setup()
drv.window(run.seconds)
drv.release()
drv.read()
out["control"] = {k: all(c.ok for c in checks_from(run, v)) for k, v in drv.control().items()}

# the exchange left out: each chip's update from its own rows alone, as
# the first chip would apply it
from repro.engine import execute as engine

loss = engine.loss

def own_rows(plan, params, batch):
    n = batch["labels"].shape[0] // 4
    return loss(plan, params, {k: v[:n] for k, v in batch.items()})

engine.loss = own_rows
out["no_exchange"] = execute(make_run(root, "train4"))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]),
    )
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CHILD), str(tmp_path_factory.mktemp("dp4")),
         os.path.join(ROOT, "BENCHMARK.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_device_training_run_is_correct(four):
    out = four["sound"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"train_step_s", "setup_s"}


def test_exchange_left_out_is_not_correct(four):
    out = four["no_exchange"]
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("control", ["int8", "half_batch", "no_exchange"])
def test_four_device_controls_fail_the_check(four, control):
    assert four["control"][control] is False
