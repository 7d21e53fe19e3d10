"""Distributed semantics: logical sharding rules, multi-device equivalence
(run in subprocesses with forced host device counts), compression,
pipeline, and the scaled-down dry-run."""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (activate_mesh, logical_to_spec,
                                        param_logical_axes)
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    # fake host devices need the CPU platform; never let the child probe
    # TPU (libtpu-installed, TPU-less containers hang in TPU client init)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# -- rule resolution (no devices needed) --------------------------------------

def test_logical_rules_divisibility_fallback():
    mesh = make_mesh((1,), ("model",))  # single device, axis size 1
    with activate_mesh(mesh):
        # axis size 1 -> never shard
        assert logical_to_spec(["heads"], [56]) == P(None)


def test_param_axis_patterns():
    assert param_logical_axes("layer/q_proj/kernel", 2) == ("embed",
                                                            "qkv_dim")
    assert param_logical_axes("stack/slot0/moe/experts/w_gate", 3) == \
        ("experts", "embed", "ff")
    # stacked (scan) leading dim resolves to None
    assert param_logical_axes("stack/slot0/attn/q_proj/kernel", 3) == \
        (None, "embed", "qkv_dim")
    assert param_logical_axes("embed/table", 2) == ("vocab", "embed")
    assert param_logical_axes("stack/slot0/mamba/conv1d/w", 3) == \
        (None, "conv_k", "d_inner")


def test_spec_resolution_on_fake_mesh():
    code = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import activate_mesh, logical_to_spec
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model=4)
    with activate_mesh(mesh):
        # 56 heads do NOT divide model=4? 56/4=14 -> shard
        assert logical_to_spec(["heads"], [56]) == P("model")
        # 55 heads do not divide 4 -> replicate (fallback, no error)
        assert logical_to_spec(["heads"], [55]) == P(None)
        # batch prefers ("pod","data") but pod absent -> ("data",)
        assert logical_to_spec(["batch", None], [8, 3]) == P("data", None)
        # two axes never doubly assign one mesh axis
        spec = logical_to_spec(["heads", "ff"], [8, 8])
        assert tuple(spec) in ((("model"), None), ("model", None))
    print("ok")
    """
    assert "ok" in run_py(code, devices=8)


# -- multi-device numerics ------------------------------------------------------

def test_sharded_train_step_matches_single_device():
    """The same train step on a (2,2) mesh and on 1 device produce the same
    loss and parameter update (GSPMD partitioning is semantics-preserving
    for our sharding rules)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke
    from repro.nn.models import build_model
    from repro.distributed import (StepConfig, activate_mesh,
                                   make_train_state, make_train_step,
                                   state_pspec)
    from repro.distributed.steps import _to_shardings, batch_pspec
    from repro.launch.mesh import make_host_mesh
    cfg = get_smoke("granite-3-2b")
    model = build_model(cfg)
    state = make_train_state(model, jax.random.PRNGKey(0))
    rngb = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rngb.integers(0, cfg.vocab, (4, 17)),
                                   jnp.int32)}
    scfg = StepConfig(warmup_steps=1, total_steps=10)
    # single device
    s1, m1 = jax.jit(make_train_step(model, scfg))(state, batch)
    # sharded
    mesh = make_host_mesh(model=2)
    with activate_mesh(mesh) as ctx, mesh:
        model2 = build_model(cfg, tp=2)
        step = make_train_step(model2, scfg)
        sspec = state_pspec(state, ctx)
        sshard = _to_shardings(sspec, mesh)
        state2 = jax.device_put(state, sshard)
        batch2 = jax.device_put(batch, _to_shardings(
            batch_pspec(batch, ctx), mesh))
        s2, m2 = jax.jit(step, in_shardings=(sshard, None),
                         out_shardings=(sshard, None))(state2, batch2)
    print("loss_diff", abs(float(m1["loss"]) - float(m2["loss"])))
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))),
        s1["params"], s2["params"])
    print("max_param_diff", max(jax.tree_util.tree_leaves(d)))
    """
    out = run_py(code, devices=4, timeout=560)
    loss_diff = float(out.split("loss_diff")[1].split()[0])
    param_diff = float(out.split("max_param_diff")[1].split()[0])
    assert loss_diff < 1e-4
    assert param_diff < 5e-3   # adamw rsqrt amplifies tiny reduction skew


def test_pallas_cnn_grads_data_parallel_match_single_device():
    """The Pallas conv kernels cannot be partitioned by XLA, so under a
    mesh each device runs them on its own images (shard_map over the
    batch); loss and weight grads on a (4, 1) mesh equal one device's."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import CNN_SMOKES
    from repro.distributed import activate_mesh
    from repro.engine import ExecutionPolicy
    from repro.launch.mesh import make_host_mesh
    from repro.nn.models import build_model
    cfg = CNN_SMOKES["alexnet"]
    model = build_model(cfg, policy=ExecutionPolicy(substrate="pallas"))
    assert {d["substrate"] for d in model.plan.describe()} == {"interpret"}
    params = model.init(jax.random.PRNGKey(0))
    H, W = cfg.input_hw
    batch = {"images": jax.random.normal(jax.random.PRNGKey(1),
                                         (8, H, W, cfg.layers[0].M)),
             "labels": jnp.arange(8, dtype=jnp.int32) % cfg.n_classes}
    f = jax.value_and_grad(lambda p, b: model.loss(p, b)[0])
    l1, g1 = jax.jit(f)(params, batch)
    mesh = make_host_mesh()
    with activate_mesh(mesh), mesh:
        txt = jax.jit(f).lower(params, batch).as_text()
        l4, g4 = jax.jit(f)(params, batch)
    assert "shard_map" in txt or "sdy.manual_computation" in txt
    gd = max(float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))
             for a, b in zip(jax.tree_util.tree_leaves(g4),
                             jax.tree_util.tree_leaves(g1)))
    print("loss_diff", abs(float(l4) - float(l1)), "grad_rel", gd)
    """
    out = run_py(code, devices=4)
    assert float(out.split("loss_diff")[1].split()[0]) < 1e-5
    assert float(out.split("grad_rel")[1].split()[0]) < 1e-5


def test_compressed_grads_close_and_ef():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.compression import compressed_grads, init_ef
    from repro.launch.mesh import make_host_mesh
    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"])**2), {}
    key = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(key, (16, 8))}
    b = {"x": jax.random.normal(key, (32, 16)),
         "y": jax.random.normal(key, (32, 8))}
    mesh = make_host_mesh(model=2)
    with mesh:
        (_, _), g1 = jax.jit(lambda p, b: jax.value_and_grad(
            loss_fn, has_aux=True)(p, b))(p, b)
        (_, _), g2 = jax.jit(
            lambda p, b: compressed_grads(loss_fn, p, b, mesh))(p, b)
        rel = float(jnp.abs(g2["w"] - g1["w"]).max()
                    / jnp.abs(g1["w"]).max())
        ef = init_ef(p, mesh)
        (_, _), g3, ef2 = jax.jit(lambda p, b, e: compressed_grads(
            loss_fn, p, b, mesh, e))(p, b, ef)
        # error feedback holds exactly the quantization residual
        resid = float(jnp.abs(ef2["w"]).max())
    print("rel", rel, "resid", resid)
    """
    out = run_py(code)
    rel = float(out.split("rel")[1].split()[0])
    resid = float(out.split("resid")[1].split()[0])
    assert rel < 0.02      # int8 quantization error bound
    assert resid > 0


def test_pipeline_matches_sequential():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_run
    from repro.launch.mesh import make_mesh
    key = jax.random.PRNGKey(0)
    mesh = make_mesh((4, 2), ("pod", "data"))
    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])
    sp = {"w": jax.random.normal(key, (4, 8, 8)) * 0.5}
    x = jax.random.normal(key, (6, 3, 8))
    with mesh:
        out = jax.jit(lambda p, x: pipeline_run(
            stage_fn, p, x, mesh=mesh, axis="pod"))(sp, x)
    ref = x
    for s in range(4):
        ref = jnp.tanh(ref @ sp["w"][s])
    print("err", float(jnp.abs(out - ref).max()))
    """
    out = run_py(code)
    assert float(out.split("err")[1].split()[0]) < 1e-6


def test_main_path_leaves_device_count_alone():
    """The launchers on the main path never import the dry-run modules,
    which force XLA_FLAGS to hundreds of host devices when imported."""
    code = """
    import os, sys
    before = os.environ.get("XLA_FLAGS")
    import repro.launch.serve_cnn, repro.launch.train
    bad = [m for m in ("repro.launch.dryrun", "repro.launch.dryrun_cnn",
                       "benchmarks.hillclimb") if m in sys.modules]
    assert not bad, bad
    assert os.environ.get("XLA_FLAGS") == before
    print("ok")
    """
    assert "ok" in run_py(code, devices=2)


@pytest.mark.slow
def test_dryrun_scaled_cell():
    """The real dry-run entrypoint, scaled to 8 host devices, produces a
    sane artifact for one (arch x shape x mesh) cell."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
                   PYTHONPATH=os.path.join(REPO, "src"))
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "mamba2-130m", "--shape", "decode_32k",
             "--multi-pod", "--out", d],
            capture_output=True, text=True, env=env, timeout=560)
        assert out.returncode == 0, out.stderr[-4000:]
        path = os.path.join(d, "mamba2-130m__decode_32k__multi.json")
        rec = json.load(open(path))
        assert rec["mesh"].get("pod") == 2
        assert rec["roofline"]["step_time_bound_s"] > 0
        assert rec["cost_calibrated"]["flops"] > 0
