"""The reduction from a profiler trace to device numbers."""

from __future__ import annotations

import gzip
import os

import pytest

from bench.trace_reduce import Op, Reduced


def _ops(*spec):
    return [Op(name, s, d) for name, s, d in spec]


def test_busy_time_is_the_union_of_operations_averaged_over_chips():
    red = Reduced(
        ops={
            0: _ops(("a", 0, 10), ("b", 5, 10), ("c", 30, 10)),  # busy 25
            1: _ops(("a", 0, 40)),  # busy 40
        },
        t0_ns=0,
        t1_ns=100,
    )
    assert red.busy_s == pytest.approx(32.5e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert red.seconds(lambda o: o.name == "a") == pytest.approx(25e-9)
    assert red.by_name()[0] == ("a", pytest.approx(25e-9))


def test_exposed_collective_time_leaves_out_what_compute_covers():
    red = Reduced(
        ops={0: _ops(("fusion.1", 0, 10), ("all-reduce.3", 5, 20), ("fusion.2", 20, 3))},
        t0_ns=0,
        t1_ns=30,
    )
    # the all-reduce spans [5, 25); compute covers [5, 10) and [20, 23)
    assert red.collective_exposed_s() == pytest.approx(12e-9)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    red = Reduced(
        ops={0: _ops(("x", 10, 10), ("y", 50, 10))},
        t0_ns=0,
        t1_ns=100,
        spans=[("bench.window", 0, 100), ("bench.chunk", 15, 60)],
    )
    assert red.gaps() == [(0, 10), (20, 50), (60, 100)]
    out = red.breakdown()
    assert out["idle_gaps"] == [
        ["bench.window", pytest.approx(40e-9)],
        ["bench.chunk", pytest.approx(30e-9)],
        ["bench.window", pytest.approx(10e-9)],
    ]
    assert [n for n, _ in out["device_ops"]] == ["x", "y"]


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "alexnet_serve_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def v5e(tmp_path_factory):
    """A trace recorded on one v5e: the AlexNet float serving
    executables run for buckets 64, 64, 16, 16, 1 and 1."""
    from bench.trace_reduce import reduce_file

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURE) as f:
        path.write_bytes(f.read())
    return reduce_file(str(path))


def test_a_recorded_v5e_trace_reduces_to_busy_time_and_kernels(v5e):
    from bench.roofline import is_conv_kernel

    assert list(v5e.ops) == [0]
    assert len(v5e.ops[0]) == 2062
    assert 0 < v5e.busy_s <= v5e.window_s
    assert v5e.busy_s == pytest.approx(0.11435, rel=1e-3)
    # 8 Mosaic conv calls per batch (the grouped layers take two)
    assert v5e.count(is_conv_kernel) == 48
    assert 0.07 < v5e.seconds(is_conv_kernel) < v5e.busy_s
    assert v5e.collective_exposed_s() == 0.0
    out = v5e.breakdown()
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["device_ops"][0][0] == "%multiply_reduce_fusion.6 f32[4096] fusion"
    assert all(label == "outside the harness" for label, _ in out["idle_gaps"])


def test_roofline_shares_of_the_recorded_trace_stay_under_100(v5e):
    from bench.roofline import head_loop_s, serve_conv_share, serve_head_share
    from bench.spec import Spec
    from tests.bench.benchroot import BENCH

    spec = Spec(os.path.dirname(BENCH))

    class R:
        reduced = v5e
        cfg = spec.config("alexnet")
        peaks = spec.peaks("TPU v5 lite")
        obs = {"flushes": {64: 2, 16: 2, 1: 2}}

    # one head loop per batch, holding the 9216 x 4096 weight
    assert head_loop_s(v5e, R.cfg) == pytest.approx(
        v5e.seconds(lambda o: o.name.startswith("%while")), rel=1e-9
    )
    conv = serve_conv_share(R)
    head = serve_head_share(R)
    assert 0 < conv < 100 and 0 < head < 100
