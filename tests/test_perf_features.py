"""§Perf feature tests: padded/chunked CE, seq-sharded decode, FSDP specs,
bf16 SSD scores — each must preserve semantics (they only move bytes)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.nn.layers import mask_pad_logits
from repro.nn.losses import chunked_softmax_xent, softmax_xent
from repro.nn.models import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_padded_ce_equals_sliced():
    key = jax.random.PRNGKey(0)
    B, S, d, V, Vpad = 2, 8, 16, 50, 64
    x = jax.random.normal(key, (B, S, d))
    table = jax.random.normal(jax.random.fold_in(key, 1), (Vpad, d))
    tgt = jax.random.randint(key, (B, S), 0, V)
    logits = jnp.einsum("bsd,vd->bsv", x, table)
    ce_pad = softmax_xent(mask_pad_logits(logits, V), tgt)
    ce_ref = softmax_xent(logits[..., :V], tgt)
    assert abs(float(ce_pad - ce_ref)) < 1e-6


@pytest.mark.parametrize("chunk", [16, 64, 100])
def test_chunked_ce_value_and_grad(chunk):
    key = jax.random.PRNGKey(1)
    B, S, d, V, Vpad = 2, 6, 12, 77, 96
    x = jax.random.normal(key, (B, S, d))
    table = jax.random.normal(jax.random.fold_in(key, 1), (Vpad, d))
    tgt = jax.random.randint(key, (B, S), 0, V)

    def ref(t):
        return softmax_xent(
            jnp.einsum("bsd,vd->bsv", x, t)[..., :V], tgt)

    def chk(t):
        return chunked_softmax_xent(x, t, tgt, V, chunk=chunk)

    assert abs(float(ref(table) - chk(table))) < 1e-5
    g1, g2 = jax.grad(ref)(table), jax.grad(chk)(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


def test_model_ce_impls_agree():
    cfg = get_smoke("granite-3-2b")
    m1 = build_model(cfg)
    m2 = build_model(cfg.with_overrides(ce_impl="chunked"))
    p = m1.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    l1, _ = m1.loss(p, {"tokens": toks})
    l2, _ = m2.loss(p, {"tokens": toks})
    assert abs(float(l1 - l2)) < 1e-5


def test_seqshard_decode_fallback_matches_baseline():
    cfg = get_smoke("mistral-large-123b")
    m1 = build_model(cfg)
    m2 = build_model(cfg.with_overrides(decode_kv_seqshard=True))
    p = m1.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    full, _ = m1.forward(p, toks)
    for m in (m1, m2):
        cache = m.init_cache(B, S + 2, dtype=jnp.float32)
        pre, cache = m.prefill(p, toks[:, :S - 1], cache)
        dec, _ = m.decode_step(p, toks[:, S - 1], cache, jnp.int32(S - 1))
        np.testing.assert_allclose(np.asarray(dec),
                                   np.asarray(full[:, S - 1]),
                                   rtol=3e-4, atol=3e-4)


def test_seqshard_decode_distributed():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke
    from repro.nn.models import build_model
    from repro.distributed import activate_mesh
    from repro.distributed.steps import _to_shardings, cache_pspec
    from repro.launch.mesh import make_host_mesh
    cfg = get_smoke("mistral-large-123b").with_overrides(
        n_q=8, n_kv=2, head_dim=8)
    B, S = 4, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    m_ref = build_model(cfg)
    p = m_ref.init(jax.random.PRNGKey(0))
    full, _ = m_ref.forward(p, toks)
    mesh = make_host_mesh(model=4)
    with activate_mesh(mesh) as ctx, mesh:
        m = build_model(cfg.with_overrides(decode_kv_seqshard=True), tp=4)
        cache = m.init_cache(B, S, dtype=jnp.float32)
        cache = jax.device_put(cache,
                               _to_shardings(cache_pspec(cache, ctx), mesh))
        pre, cache = jax.jit(m.prefill)(p, toks[:, :S-1], cache)
        dec, cache2 = jax.jit(m.decode_step)(p, toks[:, S-1], cache,
                                             jnp.int32(S-1))
        kv = cache2["slot0"]["kv_seq"].k
        assert "model" in str(kv.sharding.spec), kv.sharding.spec
    err = float(jnp.abs(dec - full[:, S-1]).max())
    print("err", err)
    assert err < 1e-4
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    # fake host devices need the CPU platform; never let the child probe
    # TPU (libtpu-installed, TPU-less containers hang in TPU client init)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]


def test_fsdp_pspec_shards_params_over_dp():
    code = """
    import jax, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import (activate_mesh, fsdp_pspec,
                                            param_pspec)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model=4)
    params = {"mlp": {"w_gate": {"kernel": np.zeros((64, 128))}},
              "norm": {"scale": np.zeros((64,))}}
    with activate_mesh(mesh) as ctx:
        base = param_pspec(params, ctx)
        fs = fsdp_pspec(params, ctx)
    # TP shards ff over model; FSDP additionally shards embed over data
    assert base["mlp"]["w_gate"]["kernel"] == P(None, "model")
    assert fs["mlp"]["w_gate"]["kernel"] == P("data", "model")
    print("ok")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    # fake host devices need the CPU platform; never let the child probe
    # TPU (libtpu-installed, TPU-less containers hang in TPU client init)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=360)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ok" in out.stdout


def test_ssd_bf16_close_to_f32():
    from repro.nn.mamba import mamba_dims, init_mamba, mamba_mixer
    dims = mamba_dims(32, expand=2, headdim=8, d_state=16, chunk=16)
    p = init_mamba(jax.random.PRNGKey(0), dims)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    y32, _ = mamba_mixer(p, u, dims, mode="train",
                         score_dtype=jnp.float32)
    y16, _ = mamba_mixer(p, u, dims, mode="train",
                         score_dtype=jnp.bfloat16)
    rel = float(jnp.abs(y16 - y32).max()
                / jnp.maximum(jnp.abs(y32).max(), 1e-6))
    assert rel < 0.05, rel


def test_flash_kernel_matches_module_attention():
    """The Pallas flash kernel == nn.attention's XLA streaming flash on the
    same inputs (ties the §Perf kernel to the module it replaces)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.nn.attention import flash_attention
    key = jax.random.PRNGKey(0)
    B, H, G, S, D = 1, 2, 3, 48, 16
    q5 = jax.random.normal(key, (B, S, H, G, D))
    k4 = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    v4 = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
    ref = flash_attention(q5, k4, v4, causal=True, chunk_k=16)
    # kernel layout: (B, H*G, S, D) with k/v repeated per group
    qk = q5.transpose(0, 2, 3, 1, 4).reshape(B, H * G, S, D)
    kk = jnp.repeat(k4.transpose(0, 2, 1, 3), G, axis=1)
    vk = jnp.repeat(v4.transpose(0, 2, 1, 3), G, axis=1)
    out = flash_attention_pallas(qk, kk, vk, causal=True, block_q=16,
                                 block_k=16, interpret=True)
    out = out.reshape(B, H, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_dryrun_cnn_scaled():
    """The bonus CNN dry-run (paper's own workload) compiles at scale."""
    import json
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
                   PYTHONPATH=os.path.join(REPO, "src"))
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun_cnn",
             "--arch", "vgg16", "--batch", "32", "--out", d],
            capture_output=True, text=True, env=env, timeout=560)
        assert out.returncode == 0, out.stderr[-3000:]
        rec = json.load(open(os.path.join(d, "vgg16__cnn_train__single.json")))
        assert rec["roofline"]["useful_flops_ratio"] > 0.5
