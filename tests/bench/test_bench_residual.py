"""A configuration that is not a chain joins the benchmark as files
alone: a toy residual CNN with its own layer table, reference module and
cells, added to a benchmark tree with no edit to any file there.  The
program runs no residual network, so no server is built for it."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from tests.bench.benchroot import BENCH, cpu, make_root, make_run

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _layer(name, hw, k, m, n, stride, pad, src, **extra):
    return dict(name=name, H_I=hw, W_I=hw, K=k, M=m, N=n, stride=stride, pad=pad, groups=1, **{"from": src}, **extra)


#: 16x16 input, a stem conv, one bottleneck whose 1x1 stride-2
#: projection reads the stem (not the layer before it), a global average
#: pool and a dense head.
TOY = {
    "name": "toy-residual",
    "reference": "toy_residual",
    "registry": "CNN_SMOKES",
    "program_arch": "vgg16",
    "input_hw": [16, 16],
    "in_channels": 3,
    "layers": [
        _layer("stem", 16, 3, 3, 8, 1, 1, "image"),
        _layer("b1a", 16, 1, 8, 4, 1, 0, "stem"),
        _layer("b1b", 16, 3, 4, 4, 2, 1, "b1a"),
        _layer("b1proj", 16, 1, 8, 16, 2, 0, "stem", relu=False),
        _layer("b1c", 8, 1, 4, 16, 1, 0, "b1b", add="b1proj"),
    ],
    "head_in": 16,
    "classifier": [],
    "n_classes": 10,
}

#: Per layer by hand: output side x output side x K x K x M x N MACs, and
#: input elements H_I x W_I x M x groups.
TOY_MACS = {"stem": 16 * 16 * 9 * 3 * 8, "b1a": 16 * 16 * 8 * 4, "b1b": 8 * 8 * 9 * 4 * 4,
            "b1proj": 8 * 8 * 8 * 16, "b1c": 8 * 8 * 4 * 16}
TOY_IN = {"stem": 16 * 16 * 3, "b1a": 16 * 16 * 8, "b1b": 16 * 16 * 4, "b1proj": 16 * 16 * 8, "b1c": 8 * 8 * 4}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A benchmark tree to which the toy configuration is added as new
    files and ``BENCHMARK.json`` entries only."""
    root, bench = make_root(tmp_path_factory.mktemp("toyroot"), os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    with open(os.path.join(bench, "configs", "toy-residual.json"), "w") as f:
        json.dump(TOY, f)
    shutil.copy(os.path.join(FIXTURES, "toy_residual.py"), os.path.join(bench, "reference", "toy_residual.py"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bj = json.load(f)
    bj["configs"].append({"name": "toy-residual", "source": "https://arxiv.org/abs/1512.03385",
                          "file": "bench/configs/toy-residual.json", "reduced": [], "why": "test"})
    for name, base in (("toy-serve", "offline"), ("toy-train", "train")):
        with open(os.path.join(bench, "workloads", base + ".json")) as f:
            cell = dict(json.load(f), name=name, config="toy-residual")
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(cell, f)
        bj["workloads"].append({"name": name, "config": "toy-residual", "traffic": cell["traffic"], "chips": 1,
                                "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    return root, bench


def test_the_spec_finds_every_piece_of_the_toy(toy_root):
    from bench.spec import Spec

    spec = Spec(*toy_root)
    for name in ("toy-serve", "toy-train"):
        cell = spec.cell(name)
        cfg = spec.config(cell["config"])
        assert cfg["name"] == "toy-residual"
        ref = spec.reference(cfg["reference"])
        assert all(callable(getattr(ref, f)) for f in ("init_params", "forward", "loss", "check_program"))
        spec.driver(spec.mix(cell["traffic"])["driver"])
    assert spec.reference("toy_residual") is not spec.reference("cnn")


def test_work_counts_of_the_toy_come_from_each_layer_s_own_entry():
    from bench import work

    layers = work.conv_layers(TOY)
    assert {x["name"]: x["macs"] for x in layers} == TOY_MACS
    assert {x["name"]: x["in"] for x in layers} == TOY_IN
    assert work.head_dims(TOY) == [16, 10]
    assert work.forward_flops(TOY) == 2 * (sum(TOY_MACS.values()) + 16 * 10)
    # without its own groups the projection's input would be derived from
    # the layer before it (4 channels over its 8: no whole group)
    chain = dict(TOY, layers=[{k: v for k, v in x.items() if k != "groups"} for x in TOY["layers"]])
    assert work.conv_layers(chain)[3]["in"] != TOY_IN["b1proj"]


def test_the_serving_check_reads_the_toy_s_own_reference(toy_root):
    import jax

    from bench import serving

    run = make_run(toy_root, "toy-serve")
    cpu(run)
    ref = run.spec.reference("toy_residual")
    images = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (8, 16, 16, 3)))
    params = jax.jit(lambda: ref.init_params(TOY, run.program_seed))()
    logits = np.array(jax.jit(lambda p, x: ref.forward(TOY, p, x, "f32"))(params, images))
    drv = serving.ServingDriver(run)
    drv.images, drv.image_of = images, {i: i for i in range(8)}
    assert drv.readings(dict(enumerate(logits)))["logit_err"] == 0.0
    logits[5, 2] += 0.1 * np.abs(logits[5]).max()
    assert drv.readings(dict(enumerate(logits)))["logit_err"] == pytest.approx(0.1, rel=1e-5)


def test_the_training_check_follows_the_toy_s_own_reference(toy_root):
    import jax
    import jax.numpy as jnp

    run = make_run(toy_root, "toy-train")
    cpu(run)
    ref = run.spec.reference("toy_residual")
    key = jax.random.PRNGKey(9)
    batches = [
        {"images": jax.random.normal(jax.random.fold_in(key, i), (8, 16, 16, 3)),
         "labels": jax.random.randint(jax.random.fold_in(key, 10 + i), (8,), 0, 10, jnp.int32)}
        for i in range(4)
    ]
    out = run.spec.driver("train_fixed").reference_steps(run, batches)
    params = ref.init_params(TOY, run.program_seed)
    want = float(jax.jit(lambda p, b: ref.loss(TOY, p, b["images"], b["labels"]))(params, batches[0]))
    assert out["losses"][0] == pytest.approx(want, rel=1e-5)
    assert jax.tree.structure(out["g1"]) == jax.tree.structure(params)
    assert len(out["delta"]) == len(jax.tree.leaves(params))


def test_program_config_runs_the_toy_s_own_check_against_the_program(toy_root):
    from bench import serving
    from bench.harness import RunFailed

    run = make_run(toy_root, "toy-serve")
    with pytest.raises(RunFailed, match="toy-residual has shortcuts; the program's CNNConfig is a chain"):
        serving.program_config(run)
