"""The recorded v5e trace's numbers, pinned: whatever the reduction
learns to read besides (program spans, gap labels), every number it
already gives reads the same on the recorded trace."""

from __future__ import annotations

import gzip
import os

import pytest

from bench.trace_reduce import reduce_file

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "alexnet_serve_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """What a reader sees after the recorded trace's window: AlexNet's
    float serving executables for buckets 64, 64, 16, 16, 1 and 1."""
    from bench.spec import Spec
    from tests.bench.benchroot import BENCH

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    spec = Spec(os.path.dirname(BENCH))

    class Run:
        reduced = reduce_file(str(path))
        cfg = spec.config("alexnet")
        peaks = spec.peaks("TPU v5 lite")
        obs = {"flushes": {64: 2, 16: 2, 1: 2}}

    return Run


def _read(name):
    from bench import roofline

    return {
        "busy_s": lambda r: r.reduced.busy_s,
        "window_s": lambda r: r.reduced.window_s,
        "collective_exposed_s": lambda r: r.reduced.collective_exposed_s(),
        "conv_kernel_s": roofline.conv_kernel_s,
        "conv_roofline": roofline.serve_conv_share,
    }[name]


@pytest.mark.parametrize(
    "name, value",
    [
        ("busy_s", 0.114350119),
        ("window_s", 0.131421295),
        ("collective_exposed_s", 0.0),
        ("conv_kernel_s", 0.075334896),
        ("conv_roofline", 1.7145796648873766),
    ],
)
def test_recorded_trace_numbers_are_pinned(run, name, value):
    assert _read(name)(run) == pytest.approx(value, rel=1e-12, abs=1e-15)


def test_recorded_trace_breakdown_is_pinned(run):
    out = run.reduced.breakdown()
    assert [round(s * 1e9) for _, s in out["idle_gaps"]] == [
        6881005, 3374525, 3239035, 1980836, 1594904, 3, 3, 3, 3, 3,
    ]
    assert {label for label, _ in out["idle_gaps"]} == {"outside the harness"}
    assert out["device_ops"][:3] == [
        ["%multiply_reduce_fusion.6 f32[4096] fusion", pytest.approx(0.032059316, rel=1e-12)],
        ["%while tuple while", pytest.approx(0.027040269, rel=1e-12)],
        ["%run_conv2d.8 f32[64,56,55,96] custom-call", pytest.approx(0.01483239, rel=1e-12)],
    ]
