"""Whole serving runs at a size the CPU holds: the result line, a broken
answer, and the int8 control."""

from __future__ import annotations

import json

import pytest

from bench.harness import checks_from
from tests.bench.benchroot import cpu, execute, make_run


@pytest.mark.parametrize("name, e2e", [
    ("poisson", {"serve_p95_ms", "serve_p50_ms", "setup_s"}),
    ("offline", {"serve_images_per_s", "setup_s"}),
])
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


def test_an_answer_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from repro.serve.engine import ServeEngine

    run_bucket = ServeEngine.run_bucket

    def altered(self, bucket, images):
        out = run_bucket(self, bucket, images)
        return out.at[:, 3].add(0.05 * abs(out).max())

    monkeypatch.setattr(ServeEngine, "run_bucket", altered)
    out = execute(make_run(root, "offline"))
    assert out["correct"] is False and out["checks"]["logit_err"]["value"] > 1e-3


@pytest.mark.parametrize("name", ["offline"])
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
