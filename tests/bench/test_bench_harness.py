"""The benchmark harness at a size the CPU holds: finding its pieces by
name, the yardstick's counts, seeded traffic, the refusals (no chip, a
layer off its kernel, a compile in the window), and the int8 control's
rounding."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tests.bench.benchroot import BENCH, execute, make_root, make_run

ROOT = os.path.dirname(BENCH)


# -- the benchmark's own files ---------------------------------------------


def test_benchmark_json_names_files_that_exist():
    from bench.spec import Spec

    spec = Spec(ROOT)
    bj = spec.benchmark
    for cfg in bj["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for w in bj["workloads"]:
        cell = spec.cell(w["name"])
        assert spec.config(cell["config"])["name"] == cell["config"]
        spec.driver(spec.mix(cell["traffic"])["driver"])
        for m in spec.metrics_for(w["name"], "per_layer"):
            assert callable(spec.reader(m["name"]).read)
        names = {m["name"] for m in spec.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize(
    "config, conv_g, head_m, fwd_g, head_mb",
    [("vgg16", 15.35, 123.6, 30.94, 494), ("alexnet", 0.666, 58.6, 1.45, 234)],
)
def test_work_counts_match_the_layer_tables(config, conv_g, head_m, fwd_g, head_mb):
    from bench import work
    from bench.spec import Spec

    cfg = Spec(ROOT).config(config)
    assert work.conv_macs(cfg) / 1e9 == pytest.approx(conv_g, rel=2e-3)
    assert work.head_macs(cfg) / 1e6 == pytest.approx(head_m, rel=1e-3)
    assert work.forward_flops(cfg) / 1e9 == pytest.approx(fwd_g, rel=2e-3)
    assert work.head_weight_bytes(cfg) / 1e6 == pytest.approx(head_mb, rel=3e-3)
    first = work.conv_layers(cfg)[0]["macs"]
    assert work.train_flops(cfg) == 3 * work.forward_flops(cfg) - 2 * first


def test_config_files_match_the_program():
    from bench.reference.cnn import check_config
    from bench.serving import program_config
    from bench.spec import Spec

    spec = Spec(ROOT)

    class R:
        pass

    for cfg in spec.benchmark["configs"]:
        r = R()
        r.spec = spec
        r.cfg = spec.config(cfg["name"])
        program_config(r)
        check_config(r.cfg)
        r.cfg = dict(r.cfg, n_classes=10)
        with pytest.raises(Exception, match="n_classes"):
            program_config(r)


def test_unknown_names_and_device_kinds_are_refused(root):
    from bench.spec import Spec, SpecError

    spec = Spec(*root)
    for find in (spec.cell, spec.config, spec.mix, spec.driver, spec.reader, spec.reference):
        with pytest.raises(SpecError):
            find("no-such-name")
    with pytest.raises(SpecError, match="not in peaks.json"):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_a_new_cell_and_metric_are_found_from_files_alone(tmp_path):
    """A later cell, mix, configuration and per-layer metric are new
    files and new ``BENCHMARK.json`` entries; nothing else changes."""
    from bench.spec import Spec

    root, bench = make_root(tmp_path, os.path.join(ROOT, "BENCHMARK.json"))
    with open(os.path.join(bench, "traffic", "closed-8.json"), "w") as f:
        json.dump({"driver": "closed_loop", "outstanding": 8, "image_pool": 8}, f)
    cell = dict(json.load(open(os.path.join(bench, "workloads", "offline.json"))))
    cell.update(name="shallow-cell", traffic="closed-8")
    with open(os.path.join(bench, "workloads", "shallow-cell.json"), "w") as f:
        json.dump(cell, f)
    with open(os.path.join(bench, "metrics", "flushes.shallow.py"), "w") as f:
        f.write("def read(run):\n    return float(sum(run.obs['flushes'].values()))\n")
    bj = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bj["workloads"].append(
        {"name": "shallow-cell", "config": cell["config"], "traffic": "closed-8", "chips": 1, "why": "t"}
    )
    bj["per_layer"].append(
        {"name": "flushes.shallow", "unit": "1", "better": "lower", "source": "program_counter",
         "layer": "serving", "moves": "serve_images_per_s", "workloads": ["shallow-cell"]}
    )
    bj["end_to_end"] = [
        dict(m, workloads=m["workloads"] + ["shallow-cell"]) if m["name"] == "serve_images_per_s" else m
        for m in bj["end_to_end"]
    ]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    spec = Spec(root, bench)
    assert spec.mix(spec.cell("shallow-cell")["traffic"])["outstanding"] == 8
    names = [m["name"] for m in spec.metrics_for("shallow-cell", "per_layer")]
    assert names == ["flushes.shallow"]
    assert [m["name"] for m in spec.metrics_for("shallow-cell", "end_to_end")] == ["serve_images_per_s", "setup_s"]

    class R:
        obs = {"flushes": {1: 2, 8: 3}}

    assert spec.reader("flushes.shallow").read(R()) == 5.0


# -- traffic ----------------------------------------------------------------


def test_seeded_schedules_repeat_exactly_and_share_their_gaps():
    from bench.traffic import open_poisson

    a = open_poisson.schedule(300.0, 10.0, 2**31 + 5)
    b = open_poisson.schedule(300.0, 10.0, 2**31 + 5)
    c = open_poisson.schedule(300.0, 10.0, 12345)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert abs(len(a) - len(c)) <= 1 and abs(len(a) - 3000) <= 2
    ga, gc = np.sort(np.diff(a)), np.sort(np.diff(c))
    n = min(len(ga), len(gc))
    assert np.mean(np.abs(ga[:n] - gc[:n])) < 1e-5
    assert np.all(a < 10.0) and a[0] == 0.0


def test_nearest_rank_percentile():
    from bench.traffic.open_poisson import nearest_rank, slower_than_all

    xs = list(range(1, 101))
    assert nearest_rank(xs, 95) == 95 and nearest_rank(xs, 50) == 50
    assert slower_than_all([1.0, None, 2.0], 10.0) == [1.0, 12.0, 2.0]


# -- the plain reference's int8 control ---------------------------------------


@pytest.mark.parametrize("product", ["conv", "dot"])
def test_the_int8_control_rounds_the_backward_products(product):
    """The int8 lane's input-grad and weight-grad products take the
    rounded forward operands and the rounded cotangent."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bench.reference.cnn import HI, int8_product, int8_round

    kx, kw, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    if product == "conv":
        x = jax.random.normal(kx, (2, 6, 6, 3))
        w = jax.random.normal(kw, (3, 3, 3, 4))

        def f(a, b):
            return lax.conv_general_dilated(a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                            precision=HI)
    else:
        x = jax.random.normal(kx, (5, 7))
        w = jax.random.normal(kw, (7, 3))

        def f(a, b):
            return jnp.dot(a, b, precision=HI)

    g = jax.random.normal(kg, f(x, w).shape) ** 3
    y, vjp = jax.vjp(int8_product(f), x, w)
    np.testing.assert_allclose(y, f(int8_round(x), int8_round(w)), rtol=1e-6, atol=1e-6)
    dx, dw = vjp(g)
    want_dx, want_dw = jax.vjp(f, int8_round(x), int8_round(w))[1](int8_round(g))
    np.testing.assert_allclose(dx, want_dx, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-6, atol=1e-6)
    straight = jax.vjp(f, int8_round(x), int8_round(w))[1](g)
    assert not np.allclose(dw, straight[1], rtol=1e-3, atol=1e-3)


# -- refusals -----------------------------------------------------------------


def test_a_layer_off_its_kernel_fails_themake_run(root):
    from bench.harness import RunFailed

    with pytest.raises(RunFailed, match="pallas"):
        execute(make_run(root, "offline", substrate="pallas"))


def test_a_compile_inside_the_window_fails_themake_run(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    from bench.harness import RunFailed

    run = make_run(root, "train")
    drv_cls = run.spec.driver(run.mix["driver"]).Driver
    window = drv_cls.window

    def compiling(self, seconds):
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
        window(self, seconds)

    monkeypatch.setattr(drv_cls, "window", compiling)
    with pytest.raises(RunFailed, match="compiled inside the window"):
        execute(run)


# -- the command ------------------------------------------------------------


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "alexnet-f32-offline", "--seed", "5",
         "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_the_command_refuses_a_machine_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a tpu" in p.stderr


def test_the_command_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
