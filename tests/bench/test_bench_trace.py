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


HLO = "%{name} = f32[4096]{{0}} {op}({args}), metadata={{}}"


@pytest.mark.parametrize(
    "name, op, args, collective",
    [
        ("all-reduce.3", "all-reduce", "%fusion.2", True),
        ("all-reduce-start.1", "all-reduce-start", "%p.1", True),
        ("reduce-scatter.4", "reduce-scatter", "%p.1", True),
        ("fusion.7", "fusion", "%all-reduce.3, %p.2", False),
        ("multiply.1", "multiply", "%all-gather.2, %p.2", False),
    ],
)
def test_a_collective_is_known_by_its_own_name_and_opcode(name, op, args, collective):
    from bench.trace_reduce import is_collective

    assert is_collective(Op(HLO.format(name=name, op=op, args=args), 0, 1)) is collective


def test_exposed_collective_ms_per_step_reads_the_exchange_compute_leaves_bare():
    """The reader of ``collective_exposed_ms.train``: per step, the
    all-reduce time no other operation covers; nothing where the window
    ran no collective."""
    from bench.spec import Spec
    from tests.bench.benchroot import BENCH

    read = Spec(os.path.dirname(BENCH)).reader("collective_exposed_ms.train").read

    def chip(shift):
        # per step: compute [0, 6) ms, all-reduce [4, 9) ms, the update
        # that takes its result [9, 10) ms; 3 ms of the exchange are bare
        out = []
        for step in range(2):
            t = (10 * step + shift) * 1_000_000
            out += _ops(
                (HLO.format(name="fusion.1", op="fusion", args="%p.1"), t, 6_000_000),
                (HLO.format(name="all-reduce.2", op="all-reduce", args="%fusion.1"), t + 4_000_000, 5_000_000),
                (HLO.format(name="fusion.3", op="fusion", args="%all-reduce.2"), t + 9_000_000, 1_000_000),
            )
        return out

    class R:
        reduced = Reduced(ops={0: chip(0), 1: chip(0), 2: chip(0), 3: chip(0)}, t0_ns=0, t1_ns=20_000_000)
        obs = {"steps": 2}

    assert read(R) == pytest.approx(3.0)
    R.reduced = Reduced(ops={0: _ops(("fusion.1", 0, 10)), 1: _ops(("fusion.1", 0, 10))}, t0_ns=0, t1_ns=10)
    assert read(R) is None
    R.reduced = None
    assert read(R) is None


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
    from bench.roofline import serve_conv_share
    from bench.spec import Spec
    from tests.bench.benchroot import BENCH

    spec = Spec(os.path.dirname(BENCH))

    class R:
        reduced = v5e
        cfg = spec.config("alexnet")
        peaks = spec.peaks("TPU v5 lite")
        obs = {"flushes": {64: 2, 16: 2, 1: 2}}

    assert 0 < serve_conv_share(R) < 100
