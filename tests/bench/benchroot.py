"""A benchmark tree at a size the CPU holds: the real drivers, readers,
references and peaks, with smoke configurations of the program's CNNs
in place of the published ones."""

from __future__ import annotations

import json
import os
import shutil
import time

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "bench")


def smoke_config(arch: str) -> dict:
    from repro.configs import CNN_SMOKES

    c = CNN_SMOKES[arch]
    return {
        "name": f"{arch}-smoke",
        "registry": "CNN_SMOKES",
        "program_arch": arch,
        "reference": "cnn",
        "input_hw": list(c.input_hw),
        "in_channels": c.layers[0].M,
        "layers": [
            {"name": x.name, "H_I": x.H_I, "W_I": x.W_I, "K": x.K, "M": x.M, "N": x.N,
             "stride": x.stride, "pad": x.padding}
            for x in c.layers
        ],
        "pool_after": list(c.pool_after),
        "pool": "max2x2",
        "lrn": False,
        "dropout": 0.0,
        "classifier": list(c.classifier),
        "n_classes": c.n_classes,
    }


def committed_limits(cell: str) -> dict:
    """The correctness limits of a cell of the committed benchmark."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        return json.load(f)["check"]["limits"]


#: The training cell's compared numbers at the test's size, where the
#: program runs float32 on the CPU and reads about 1e-7 on each.
TRAIN_LIMITS = {"loss1_gap": 1e-4, "grad1_gap": 1e-3, "delta3_gap": 1e-2, "out_bias_grad1_err": 1e-3}

SERVE_ARGV = ["--tuning", "off", "--substrate", "auto", "--buckets", "1,4,8", "--smoke"]

CELLS = {
    "poisson": {
        "config": "vgg16-smoke",
        "traffic": "poisson-test",
        "argv": ["--arch", "vgg16"] + SERVE_ARGV,
        "check": {"sample": 8, "limits": {"logit_err": 1e-4}},
    },
    "offline": {
        "config": "alexnet-smoke",
        "traffic": "closed-test",
        "argv": ["--arch", "alexnet"] + SERVE_ARGV,
        "check": {"sample": 8, "limits": {"logit_err": 1e-4}},
    },
    "train": {
        "config": "vgg16-smoke",
        "traffic": "train-test",
        "argv": ["--arch", "vgg16", "--tuning", "off", "--substrate", "auto", "--steps", "100", "--smoke"],
        "optimizer": {"peak_lr": 1e-3, "warmup_steps": 5, "total_steps": 100, "min_ratio": 0.1,
                      "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0,
                      "accum": 1, "no_decay": ["bias"]},
        "check": {"block": 4, "limits": TRAIN_LIMITS},
    },
}
#: The training cell on four chips (four CPU devices): two rows each.
CELLS["train4"] = dict(CELLS["train"], chips=4)

MIXES = {
    "poisson-test": {"driver": "open_poisson", "rate_hz": 200, "image_pool": 16},
    "closed-test": {"driver": "closed_loop", "outstanding": 16, "image_pool": 16},
    "train-test": {"driver": "train_fixed", "global_batch": 8, "batches": 4, "chunk": 2},
}


def make_root(tmp, benchmark_json: str):
    """A checkout-like tree under ``tmp``: ``BENCHMARK.json`` with the
    real file's metrics and the test cells, and a ``bench`` directory
    holding the real drivers and readers beside the test's files."""
    root = str(tmp)
    bench = os.path.join(root, "bench")
    for sub in ("traffic", "metrics", "reference"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    with open(benchmark_json) as f:
        bj = json.load(f)
    for arch in ("vgg16", "alexnet"):
        with open(os.path.join(bench, "configs", f"{arch}-smoke.json"), "w") as f:
            json.dump(smoke_config(arch), f)
    for name, mix in MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    bj["workloads"] = []
    for name, cell in CELLS.items():
        cell = dict({"chips": 1}, **cell, name=name, substrate="oracle")
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(cell, f)
        bj["workloads"].append({"name": name, "config": cell["config"], "traffic": cell["traffic"],
                                "chips": cell["chips"], "why": "test"})
    real = {"vgg16-f32-poisson": "poisson", "alexnet-f32-offline": "offline", "vgg16-train-b64": "train",
            "vgg16-train-dp4": "train4"}
    for group in ("end_to_end", "per_layer"):
        for m in bj[group]:
            if "workloads" in m:
                m["workloads"] = [real[w] for w in m["workloads"] if w in real]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    return root, bench


def cpu(run):
    """The CPU's devices in place of the cell's chips."""
    import jax

    chips = int(run.cell["chips"])
    if len(jax.devices()) < chips:
        raise RuntimeError(f"the cell asks for {chips} devices, the CPU has {len(jax.devices())}")
    run.devices = jax.devices()[:chips]
    run.peaks = run.spec.peaks("TPU v5 lite")


def make_run(root, name, seconds=1.0, seed=2**31 + 7, **cell_changes):
    from bench import harness
    from bench.spec import Spec

    return harness.make_run(Spec(*root), name, seed, seconds, **cell_changes)


def execute(run):
    from bench import harness

    return harness.execute(run, t_start=time.perf_counter(), find=cpu)
