"""Whole training runs at a size the CPU holds: the result line, a broken
step, and the int8 control."""

from __future__ import annotations

import json

import pytest

from bench.harness import checks_from
from tests.bench.benchroot import TRAIN_LIMITS, committed_limits, cpu, execute, make_run


def test_the_test_cell_compares_the_committed_numbers():
    """The CPU-sized training cell is judged on the same numbers as the
    committed cell, at limits for its own size."""
    assert set(TRAIN_LIMITS) == set(committed_limits("vgg16-train-b64"))


@pytest.mark.parametrize("name, e2e", [("train", {"train_step_s", "setup_s"})])
def test_result_line_keys(root, name, e2e):
    out = execute(make_run(root, name))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(out)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_loss"])
def test_a_broken_training_step_is_not_correct(root, monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from repro.engine import execute as engine
    from repro.launch import train

    if fault == "unchanged_state":
        sharded = train.sharded_train_step

        def frozen(*a, **k):
            state, step, sshard = sharded(*a, **k)

            def same(s, b):
                kept = jax.tree.map(jnp.copy, s)
                _, metrics = step(s, b)
                return kept, metrics

            same._cache_size = step._cache_size
            return state, same, sshard

        monkeypatch.setattr(train, "sharded_train_step", frozen)
    else:
        loss = engine.loss

        def broken(plan, params, batch):
            if fault == "half_batch":
                n = batch["labels"].shape[0] // 2
                batch = {k: v[:n] for k, v in batch.items()}
                return loss(plan, params, batch)
            ce, aux = loss(plan, params, batch)
            return ce * jnp.float32(1.01), aux

        monkeypatch.setattr(engine, "loss", broken)
    out = execute(make_run(root, "train"))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("name", ["train"])
def test_the_int8_control_fails_the_check(root, name):
    """The reference in int8, in the program's place, reads above the
    cell's limits on at least one number."""
    run = make_run(root, name)
    cpu(run)
    drv = run.spec.driver(run.mix["driver"]).Driver(run)
    drv.setup()
    drv.window(run.seconds)
    drv.release()
    assert all(c.ok for c in checks_from(run, drv.read()))
    readings = drv.control()
    assert not all(c.ok for c in checks_from(run, readings["int8"]))
