"""The shared serving core (repro.serve, DESIGN.md §8).

Covers: the BucketBatcher state machine on a fake clock (size flush,
deadline flush, drain, the submit-timestamp clamp), pad_batch, the
synthetic request stream's determinism and arrival processes, the serving
bit-identity property (padded-and-bucketed output == unbatched N=1
output, float AND the fused int8/int5 lanes), the compile-once guarantee
(compile_counts and the engine-level EXECUTABLE_COMPILES ledger), the
calibrated-requant requirement on the int8 lane, the Server facade —
inline open loop on a fake clock, overload policies (block/shed/degrade),
per-request deadline expiry, threaded admission with a real flush worker
(request conservation under N producer threads, deadlock guarded by
faulthandler + joined-with-timeout), the deprecation shims
(serve_stream / for_model_plan: warn AND produce identical metrics), and
ServeMetrics snapshot arithmetic incl. the admission counters.
"""
import faulthandler
import re
import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.configs import CNN_SMOKES
from repro.data.pipeline import SyntheticRequestStream
from repro.engine import ExecutionPolicy, execute, plan_model
from repro.serve import (BucketBatcher, Request, ServeConfig, ServeEngine,
                         ServeMetrics, Server, pad_batch, serve_stream,
                         stamp_payload)
from tools.analysis.runtime import sanitize_server

CFG = CNN_SMOKES["vgg16"]


class FakeClock:
    """Deterministic clock + sleep pair for driving the serve loop."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(dt, 0.0)


def _stream(n=6, process="bursts", dtype="float32", seed=0, **kw):
    return SyntheticRequestStream(
        hw=CFG.input_hw, channels=CFG.layers[0].M, n_classes=CFG.n_classes,
        n_requests=n, seed=seed, process=process, dtype=dtype, **kw)


def _float_plan_params():
    plan = plan_model(CFG, ExecutionPolicy())
    return plan, plan.init(jax.random.PRNGKey(0))


def _float_server(buckets=(1, 4), clock=None, sleep=None, **cfgkw):
    plan, params = _float_plan_params()
    cfg = ServeConfig(buckets=buckets, **cfgkw)
    kw = {}
    if clock is not None:
        kw = dict(clock=clock, sleep=sleep)
    return Server.from_plan(plan, params, cfg, **kw)


def _int8_server(buckets=(1, 4), **cfgkw):
    plan, params = _float_plan_params()
    qparams, _ = plan.quantize(params)
    requant = plan.calibrate_requant(
        qparams, _stream(dtype="uint8").sample_batch(4))
    cfg = ServeConfig(buckets=buckets, datapath="int8", **cfgkw)
    return Server.from_plan(plan, qparams, cfg, requant=requant)


def _int5_server(buckets=(1, 4), **cfgkw):
    plan, params = _float_plan_params()
    qparams, _ = plan.quantize_int5(params)
    requant = plan.calibrate_requant_int5(
        qparams, _stream(dtype="uint8").sample_batch(4))
    cfg = ServeConfig(buckets=buckets, datapath="int5", **cfgkw)
    return Server.from_plan(plan, qparams, cfg, requant=requant)


@pytest.fixture
def deadlock_guard():
    """A stuck thread must fail the suite fast, not hang CI: dump all
    stacks and hard-exit if a threaded test overruns (pytest-timeout
    covers this in CI; faulthandler covers minimal local environments)."""
    faulthandler.dump_traceback_later(180, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


# ---------------------------------------------------------------------------
# BucketBatcher: the pad-and-bucket admission state machine
# ---------------------------------------------------------------------------


def test_batcher_size_flush():
    clk = FakeClock()
    b = BucketBatcher(buckets=(2, 4), max_delay_s=1.0, clock=clk)
    assert b.poll() is None
    for _ in range(4):
        b.submit("img")
    bucket, reqs = b.poll()
    assert bucket == 4 and len(reqs) == 4
    assert b.depth == 0 and b.poll() is None


def test_batcher_deadline_flush():
    clk = FakeClock()
    b = BucketBatcher(buckets=(2, 4), max_delay_s=0.01, clock=clk)
    b.submit("a")
    assert b.poll() is None  # under-full, deadline not expired
    assert b.next_deadline() == pytest.approx(0.01)
    clk.t = 0.02
    bucket, reqs = b.poll()
    assert bucket == 2 and len(reqs) == 1  # padded into the smallest cover


def test_batcher_drain_and_bucket_for():
    clk = FakeClock()
    b = BucketBatcher(buckets=(2, 4), max_delay_s=10.0, clock=clk)
    for _ in range(3):
        b.submit("x")
    bucket, reqs = b.poll(force=True)
    assert bucket == 4 and len(reqs) == 3
    assert b.bucket_for(1) == 2 and b.bucket_for(3) == 4


def test_batcher_submit_clamps_backwards_timestamp():
    """Regression: a caller-supplied `now` behind the monotone clock used
    to make the deadline flush fire early (a backdated t_submit ages out
    instantly); one ahead of the clock made it fire late or never.  Both
    are clamped into [previous submit, clock()]."""
    clk = FakeClock()
    clk.t = 1.0
    b = BucketBatcher(buckets=(4,), max_delay_s=0.01, clock=clk)
    # Backdated below the batcher's monotone floor (construction at t=1.0):
    # an unclamped t_submit=0.0 would have expired its deadline already.
    r = b.submit("a", now=0.0)
    assert r.t_submit == 1.0
    assert b.poll() is None  # NOT an instant deadline flush
    assert b.next_deadline() == pytest.approx(1.01)
    # Future timestamp: unclamped, next_deadline would sit at 100.01 and
    # the oldest-request contract ("ships within max_delay_s") would slip.
    clk.t = 1.005
    r2 = b.submit("b", now=100.0)
    assert r2.t_submit == pytest.approx(1.005)
    # Behind the previous submit: clamps up to the queue's monotone floor.
    r3 = b.submit("c", now=1.001)
    assert r3.t_submit >= r2.t_submit
    clk.t = 1.02
    bucket, reqs = b.poll()  # q[0]'s (clamped) deadline has now passed
    assert len(reqs) == 3


def test_batcher_purge_expired_on_fake_clock():
    clk = FakeClock()
    b = BucketBatcher(buckets=(4,), max_delay_s=10.0, clock=clk)
    b.submit("a", deadline_s=0.05)
    keep = b.submit("b")  # no deadline: never expires
    b.submit("c", deadline_s=0.2)
    assert b.purge_expired() == []
    clk.t = 0.1
    expired = b.purge_expired()
    assert [r.payload for r in expired] == ["a"]
    assert b.depth == 2
    clk.t = 0.3
    assert [r.payload for r in b.purge_expired()] == ["c"]
    assert b.depth == 1 and b.poll(force=True)[1] == [keep]


@settings(max_examples=10)
@given(n=st.integers(min_value=0, max_value=12))
def test_batcher_conserves_requests(n):
    """Property: every submitted request comes back out exactly once, in
    order, whatever mix of size- and force-flushes drains the queue."""
    clk = FakeClock()
    b = BucketBatcher(buckets=(2, 4), max_delay_s=10.0, clock=clk)
    rids = [b.submit(i).rid for i in range(n)]
    out = []
    while True:
        got = b.poll(force=True)
        if got is None:
            break
        bucket, reqs = got
        assert len(reqs) <= bucket
        out.extend(r.rid for r in reqs)
    assert out == rids and b.depth == 0


def test_pad_batch_zero_pads():
    imgs = [np.ones((4, 4, 3), np.float32) * (i + 1) for i in range(3)]
    out = pad_batch(imgs, 4)
    assert out.shape == (4, 4, 4, 3)
    np.testing.assert_array_equal(out[:3], np.stack(imgs))
    np.testing.assert_array_equal(out[3], 0)


# ---------------------------------------------------------------------------
# ServeConfig: the frozen serving policy object
# ---------------------------------------------------------------------------


def test_serve_config_frozen_hashable_and_normalized():
    a = ServeConfig(buckets=(4, 1, 4), overload="shed", queue_capacity=8)
    b = ServeConfig(buckets=(1, 4), overload="shed", queue_capacity=8)
    assert a == b and hash(a) == hash(b)
    assert a.buckets == (1, 4)
    assert a.max_delay_s == pytest.approx(0.005)
    with pytest.raises(ValueError, match="overload"):
        ServeConfig(overload="panic")
    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=())
    with pytest.raises(ValueError, match="datapath"):
        ServeConfig(datapath="int4")
    with pytest.raises(ValueError, match="queue_capacity"):
        ServeConfig(queue_capacity=-1)


def test_serve_config_from_cli_args():
    """The shared launcher flags (launch.cli.serving_parent) map through
    ServeConfig.from_args — one mapping for both serving launchers."""
    import argparse

    from repro.launch.cli import serving_parent

    ap = argparse.ArgumentParser(parents=[serving_parent()])
    args = ap.parse_args(
        ["--buckets", "1,8", "--max-delay-ms", "2.5", "--queue-capacity",
         "32", "--overload", "degrade", "--request-timeout-ms", "40"])
    args.int8 = True
    cfg = ServeConfig.from_args(args)
    assert cfg == ServeConfig(buckets=(1, 8), max_delay_ms=2.5,
                              queue_capacity=32, overload="degrade",
                              datapath="int8", request_timeout_ms=40.0)
    # overrides pin fields a launcher's CLI does not expose (LM: --batch)
    assert ServeConfig.from_args(args, buckets=(4,),
                                 datapath="float").buckets == (4,)


# ---------------------------------------------------------------------------
# SyntheticRequestStream: deterministic arrival-timed requests
# ---------------------------------------------------------------------------


def test_stream_deterministic_in_seed():
    a, b = _stream(process="poisson", seed=3), _stream(process="poisson",
                                                       seed=3)
    for (ta, xa, la), (tb, xb, lb) in zip(a, b):
        assert ta == tb and la == lb
        np.testing.assert_array_equal(xa, xb)
    assert not np.array_equal(_stream(process="poisson", seed=4)
                              .arrival_times(), a.arrival_times())


def test_stream_arrival_processes():
    uni = _stream(n=5, process="uniform", rate_hz=10.0).arrival_times()
    np.testing.assert_allclose(uni, np.arange(5) / 10.0)
    poi = _stream(n=8, process="poisson").arrival_times()
    assert poi[0] == 0.0 and (np.diff(poi) >= 0).all() and poi[-1] > 0
    bur = _stream(n=7, process="bursts", burst_sizes=(1, 2),
                  gap_s=0.5).arrival_times()
    # bursts cycle (1, 2): instants 0.0, 0.5, 1.0, ... carry 1,2,1,2,... reqs
    np.testing.assert_allclose(bur, [0.0, 0.5, 0.5, 1.0, 1.5, 1.5, 2.0])


def test_stream_uint8_dtype_for_int8_lane():
    img, _ = _stream(dtype="uint8").image_at(0)
    assert img.dtype == np.uint8
    assert _stream().image_at(0)[0].dtype == np.float32


# ---------------------------------------------------------------------------
# the serving bit-identity property (the reason serve_forward exists)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("datapath", ["float", "int8", "int5"])
@pytest.mark.parametrize("n", [1, 3, 4])
def test_bucketed_equals_unbatched_bitwise(datapath, n):
    """Padded-and-bucketed inference is bit-identical, per image, to the
    unbatched N=1 path — on the float lane (per-image FC head via
    serve_forward) and the fused integer lanes (calibrated requant; int5
    is the MSR weight lane, DESIGN.md §9.3)."""
    srv = {"float": _float_server, "int8": _int8_server,
           "int5": _int5_server}[datapath]()
    eng = srv.engine
    imgs = _stream(dtype="float32" if datapath == "float" else "uint8"
                   ).sample_batch(n)
    batched = eng.infer(imgs)
    assert batched.shape[0] == n
    for i in range(n):
        single = eng.infer(imgs[i:i + 1])
        np.testing.assert_array_equal(batched[i], single[0])


@pytest.mark.parametrize("n", [execute.HEAD_ROWS + 1, 70, 2 * execute.HEAD_ROWS])
def test_bucketed_equals_unbatched_bitwise_across_head_blocks(n):
    """Past HEAD_ROWS images the float lane's head runs block by block
    (``lax.map`` over fixed (HEAD_ROWS, K) blocks); every image still
    bit-matches its unbatched N=1 answer, which runs in one block."""
    eng = _float_server(buckets=(1, 2 * execute.HEAD_ROWS)).engine
    imgs = _stream(n=n).sample_batch(n)
    batched = eng.infer(imgs)
    assert batched.shape[0] == n
    singles = np.concatenate([eng.infer(imgs[i:i + 1]) for i in range(n)])
    np.testing.assert_array_equal(batched, singles)


@pytest.mark.parametrize("n", [1, 4, execute.HEAD_ROWS, 70])
def test_serve_head_dots_keep_head_rows_at_every_bucket(n):
    """The compiled float executable's head dots have HEAD_ROWS rows at
    every bucket, so a compiler rewrite that made their row count follow
    the bucket (and so their bits) fails here; a loop over blocks appears
    only past HEAD_ROWS images."""
    plan, _ = _float_plan_params()
    hlo = execute.executable_for(plan, n, "float").as_text()
    rows = [int(m) for m in re.findall(r"= f32\[(\d+),\d+\]\S* dot\(", hlo)]
    assert len(rows) == len(CFG.classifier) + 1
    assert set(rows) == {execute.HEAD_ROWS}
    assert ("while" in hlo) == (n > execute.HEAD_ROWS)


def test_serve_forward_matches_training_forward_numerically():
    """serve_forward changes only the FC head's blocking (fixed row
    blocks), so it must agree with the training forward to float tolerance
    and produce identical argmax classes."""
    plan, params = _float_plan_params()
    x = _stream().sample_batch(2)
    a = np.asarray(execute.forward(plan, params, x))
    b = np.asarray(execute.serve_forward(plan, params, x))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


# ---------------------------------------------------------------------------
# compile-once: the no-retrace guarantee
# ---------------------------------------------------------------------------


def test_engine_compiles_each_bucket_exactly_once():
    srv = _float_server(buckets=(1, 4))
    eng = srv.engine
    assert len(eng.compile_counts) == 2
    # repeated warmup + serving traffic never rebuilds an executable
    eng.warmup()
    for _ in range(3):
        eng.infer(_stream().sample_batch(3))
    assert all(v == 1 for v in eng.compile_counts.values())
    # the engine-seam ledger agrees: every (plan, batch, datapath) compiled
    # at most once for the life of the process
    assert all(v == 1 for v in execute.EXECUTABLE_COMPILES.values())


def test_executable_keys_are_device_stamped():
    srv = _float_server(buckets=(1,))
    backend = jax.default_backend()
    (key,) = srv.engine.compile_counts
    assert key.startswith(f"{backend}-")
    assert key.endswith("n1")


def test_int8_server_requires_calibrated_requant():
    plan, params = _float_plan_params()
    qparams, _ = plan.quantize(params)
    with pytest.raises(ValueError, match="requant"):
        Server.from_plan(plan, qparams,
                         ServeConfig(buckets=(1,), datapath="int8"))


def test_infer_rejects_oversized_batch():
    srv = _float_server(buckets=(1, 4))
    with pytest.raises(ValueError, match="exceeds"):
        srv.engine.infer(_stream().sample_batch(5))


# ---------------------------------------------------------------------------
# the Server facade: inline open loop on a fake clock
# ---------------------------------------------------------------------------


def test_run_stream_inline_flushes_every_bucket_and_serves_all():
    clk = FakeClock()
    srv = _float_server(buckets=(1, 4), clock=clk, sleep=clk.sleep,
                        max_delay_ms=10.0)
    stream = _stream(n=10, process="bursts", burst_sizes=(1, 4), gap_s=0.1)
    metrics = srv.run_stream(stream)
    assert metrics.total_images == 10
    for b in srv.engine.buckets:
        assert metrics.flushes(b) >= 1, f"bucket {b} never flushed"
    assert all(r.result is not None for r in metrics.requests)
    assert all(r.status == "served" for r in metrics.requests)
    assert all(v == 1 for v in srv.engine.compile_counts.values())
    assert metrics.wall_s and metrics.wall_s > 0
    tot = metrics.snapshot()["totals"]
    assert tot["submitted"] == 10 and tot["shed"] == 0 and tot["expired"] == 0
    # every request's served result is the unbatched answer for its image
    for r, (t, img, label) in zip(metrics.requests, _stream(n=10)):
        np.testing.assert_array_equal(
            r.result, srv.engine.infer(img[None])[0])


def test_run_stream_inline_deadline_flush_under_trickle():
    """A trickle below every bucket size still ships: the deadline flush
    pads each request into the smallest bucket within max_delay."""
    clk = FakeClock()
    srv = _float_server(buckets=(4,), clock=clk, sleep=clk.sleep,
                        max_delay_ms=5.0)
    stream = _stream(n=3, process="uniform", rate_hz=10.0)  # 100 ms apart
    metrics = srv.run_stream(stream)
    assert metrics.total_images == 3
    assert metrics.flushes(4) == 3  # each arrival aged out alone
    snap = metrics.snapshot()
    assert snap["per_bucket"]["4"]["pad_waste"] == pytest.approx(0.75)
    # latency = queueing delay (deadline) + engine time, never negative
    assert snap["per_bucket"]["4"]["p50_ms"] >= 5.0


def test_overload_shed_rejects_past_capacity():
    """shed: a full admission queue rejects instead of queueing — the
    request comes back terminal (status 'shed', done set, no result), and
    conservation (served + shed == submitted) holds at drain."""
    clk = FakeClock()
    srv = _float_server(buckets=(4,), clock=clk, sleep=clk.sleep,
                        max_delay_ms=1e6, queue_capacity=2, overload="shed")
    # burst of 6 at one instant: 2 admitted (the bucket never fills, the
    # deadline never fires, so nothing drains the queue mid-burst), then
    # the queue is full and the remaining 4 are shed; the end-of-stream
    # drain serves the 2 queued ones
    stream = _stream(n=6, process="bursts", burst_sizes=(6,), gap_s=1.0)
    metrics = srv.run_stream(stream)
    tot = metrics.snapshot()["totals"]
    assert tot["submitted"] == 6
    assert tot["images"] == 2 and tot["shed"] == 4
    shed = [r for r in metrics.requests if r.status == "shed"]
    assert len(shed) == tot["shed"]
    assert all(r.done.is_set() and r.result is None for r in shed)
    rids = [r.rid for r in metrics.requests]
    assert len(set(rids)) == len(rids)


def test_overload_degrade_ships_smaller_buckets_eagerly():
    """degrade: over capacity, ship what is queued into the smallest
    covering bucket NOW instead of waiting to fill the largest."""
    clk = FakeClock()
    srv = _float_server(buckets=(2, 8), clock=clk, sleep=clk.sleep,
                        max_delay_ms=1e6, queue_capacity=2,
                        overload="degrade")
    stream = _stream(n=8, process="bursts", burst_sizes=(8,), gap_s=1.0)
    metrics = srv.run_stream(stream)
    tot = metrics.snapshot()["totals"]
    assert tot["images"] == 8 and tot["shed"] == 0
    # the full-size bucket never filled: everything shipped degraded
    assert metrics.flushes(2) == 4
    assert metrics.flushes(8) == 0


def test_overload_block_inline_caps_queue_depth():
    """block in the inline loop: the caller IS the flush worker, so
    hitting capacity drains synchronously — depth never exceeds cap and
    nothing is shed."""
    clk = FakeClock()
    srv = _float_server(buckets=(4,), clock=clk, sleep=clk.sleep,
                        max_delay_ms=1e6, queue_capacity=2,
                        overload="block")
    stream = _stream(n=6, process="bursts", burst_sizes=(6,), gap_s=1.0)
    metrics = srv.run_stream(stream)
    tot = metrics.snapshot()["totals"]
    assert tot["images"] == 6 and tot["shed"] == 0
    snap = metrics.snapshot()
    assert snap["per_bucket"]["4"]["queue_depth_max"] <= 2


def test_request_timeout_expires_queued_work():
    """Per-request deadlines: work still queued past its deadline is
    expired (no result, status 'expired'), never served stale."""
    clk = FakeClock()
    srv = _float_server(buckets=(4,), clock=clk, sleep=clk.sleep,
                        max_delay_ms=1e6,  # deadline flush disabled
                        request_timeout_ms=5.0)
    stream = _stream(n=3, process="uniform", rate_hz=10.0)  # 100 ms apart
    metrics = srv.run_stream(stream)
    tot = metrics.snapshot()["totals"]
    # the first two requests sat queued past their 5 ms deadline while the
    # loop slept to the next arrival; the last one was still fresh at the
    # end-of-stream drain and is served, not dropped
    assert tot["expired"] == 2 and tot["images"] == 1
    expired = [r for r in metrics.requests if r.status == "expired"]
    assert len(expired) == 2
    assert all(r.result is None for r in expired)
    assert metrics.requests[-1].status == "served"
    assert tot["images"] + tot["shed"] + tot["expired"] == tot["submitted"]


# ---------------------------------------------------------------------------
# threaded admission: producer threads + the dedicated flush worker
# ---------------------------------------------------------------------------


def test_threaded_submit_conserves_requests(deadlock_guard, retrace_sentinel):
    """Property: N producer threads submitting concurrently conserve
    requests exactly — served + shed + expired == submitted, every
    request terminal, no duplicate rids — under a bounded queue with the
    shed policy (real clock, real flush worker).  Runs under the runtime
    sanitizer: lock-order cycles or unguarded cv-state access anywhere in
    the producer/worker interleaving fail the test."""
    srv = _float_server(buckets=(1, 4), max_delay_ms=2.0,
                        queue_capacity=8, overload="shed")
    registry = sanitize_server(srv)
    retrace_sentinel.arm()          # engine warmed at construction
    n_threads, per_thread = 4, 12
    results = [[] for _ in range(n_threads)]

    def producer(k):
        imgs = _stream(n=per_thread, seed=k).sample_batch(per_thread)
        for i in range(per_thread):
            results[k].append(srv.submit(imgs[i]))

    threads = [threading.Thread(target=producer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "producer thread deadlocked"
    srv.drain()
    srv.close()
    reqs = [r for rs in results for r in rs]
    assert len(reqs) == n_threads * per_thread
    assert all(r.done.is_set() for r in reqs)
    statuses = [r.status for r in reqs]
    assert statuses.count("pending") == 0
    tot = srv.metrics.snapshot()["totals"]
    assert tot["submitted"] == len(reqs)
    assert (statuses.count("served") + statuses.count("shed")
            + statuses.count("expired")) == len(reqs)
    assert tot["images"] == statuses.count("served")
    assert tot["shed"] == statuses.count("shed")
    assert tot["expired"] == statuses.count("expired")
    rids = [r.rid for r in reqs]
    assert len(set(rids)) == len(rids), "duplicate request ids"
    assert all(v == 1 for v in srv.engine.compile_counts.values())
    assert registry.errors == [], registry.errors
    # served results are the bit-exact unbatched answers
    for k in range(n_threads):
        imgs = _stream(n=per_thread, seed=k).sample_batch(per_thread)
        for i, r in enumerate(results[k]):
            if r.status == "served":
                np.testing.assert_array_equal(
                    r.result, srv.engine.infer(imgs[i:i + 1])[0])


def test_threaded_run_stream_serves_all_and_overlaps(deadlock_guard,
                                                     retrace_sentinel):
    """Saturating load through producer threads: everything is served
    (block policy), compile-once holds, and the flush worker's
    double-buffered staging actually overlapped transfers with compute
    (overlapped > 0 — with a deep queue every non-first dispatch finds a
    prior bucket still in flight).  Sanitized: the saturating block-policy
    path exercises the cv-wait/notify edges hardest."""
    srv = _float_server(buckets=(1, 4), max_delay_ms=5.0)
    registry = sanitize_server(srv)
    retrace_sentinel.arm()
    stream = _stream(n=48, process="bursts", burst_sizes=(48,), gap_s=0.0)
    metrics = srv.run_stream(stream, producers=4)
    srv.close()
    tot = metrics.snapshot()["totals"]
    assert tot["images"] == 48 == tot["submitted"]
    assert tot["shed"] == 0 and tot["expired"] == 0
    assert tot["overlapped"] >= 1
    assert all(v == 1 for v in srv.engine.compile_counts.values())
    assert metrics.wall_s and metrics.wall_s > 0
    assert registry.errors == [], registry.errors


def test_threaded_expiry_and_closed_submit(deadlock_guard):
    """The worker expires pre-expired queued work instead of serving it,
    and a closed Server rejects new submissions.  Sanitized: close() walks
    the full drain/join/teardown edge of the lock protocol."""
    srv = _float_server(buckets=(4,), max_delay_ms=1.0)
    registry = sanitize_server(srv)
    srv.start()
    r = srv.submit(_stream().sample_batch(1)[0], deadline_s=-1.0)
    assert r.done.wait(30), "expiry never delivered"
    assert r.status == "expired" and r.result is None
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(_stream().sample_batch(1)[0])
    assert registry.errors == [], registry.errors


# ---------------------------------------------------------------------------
# deprecation shims: serve_stream / for_model_plan warn and delegate
# ---------------------------------------------------------------------------


def test_for_model_plan_shim_warns_and_matches_facade():
    plan, params = _float_plan_params()
    with pytest.warns(DeprecationWarning, match="for_model_plan"):
        eng = ServeEngine.for_model_plan(plan, params, buckets=(1, 4))
    srv = Server.from_plan(plan, params, ServeConfig(buckets=(1, 4)))
    assert isinstance(eng, ServeEngine)
    assert eng.buckets == srv.engine.buckets
    assert set(eng.compile_counts) == set(srv.engine.compile_counts)
    imgs = _stream().sample_batch(3)
    np.testing.assert_array_equal(eng.infer(imgs), srv.engine.infer(imgs))


def test_serve_stream_shim_warns_and_metrics_identical():
    """The old open-loop entry point must keep producing byte-identical
    metrics through the Server facade it now delegates to."""
    stream_kw = dict(n=10, process="bursts", burst_sizes=(1, 4), gap_s=0.1)
    plan, params = _float_plan_params()

    clk_old = FakeClock()
    with pytest.warns(DeprecationWarning, match="serve_stream"):
        eng = ServeEngine.build_for_plan(plan, params, buckets=(1, 4))
        old = serve_stream(eng, _stream(**stream_kw), max_delay_s=0.01,
                           clock=clk_old, sleep=clk_old.sleep)

    clk_new = FakeClock()
    srv = Server.from_plan(plan, params,
                           ServeConfig(buckets=(1, 4), max_delay_ms=10.0),
                           clock=clk_new, sleep=clk_new.sleep)
    new = srv.run_stream(_stream(**stream_kw))
    assert old.snapshot() == new.snapshot()
    for a, b in zip(old.requests, new.requests):
        assert a.status == b.status == "served"
        np.testing.assert_array_equal(a.result, b.result)


# ---------------------------------------------------------------------------
# metrics arithmetic + the serve JSON schema header
# ---------------------------------------------------------------------------


def test_metrics_snapshot_arithmetic():
    m = ServeMetrics(buckets=(1, 4))
    m.record_flush(4, 3, batch_s=0.01, latencies_s=[0.011, 0.012, 0.013],
                   queue_depth=2)
    m.record_flush(1, 1, batch_s=0.002, latencies_s=[0.003])
    m.wall_s = 0.1
    snap = m.snapshot()
    assert m.total_images == 4 and m.flushes(4) == 1
    b4 = snap["per_bucket"]["4"]
    assert b4["images"] == 3 and b4["pad_waste"] == 0.25
    # a bucket's rate is over the run's wall-clock once it is set
    assert b4["images_per_s"] == pytest.approx(30.0)
    assert b4["queue_depth_max"] == 2
    tot = snap["totals"]
    assert tot["images"] == 4 and tot["flushes"] == 2
    assert tot["pad_waste"] == pytest.approx(1 / 5)
    assert tot["images_per_s"] == pytest.approx(40.0)
    assert tot["p99_ms"] >= tot["p50_ms"] > 0
    # flushes given only batch_s add up plainly
    assert tot["busy_s"] == pytest.approx(0.012)
    m.wall_s = None
    assert m.snapshot()["per_bucket"]["4"]["images_per_s"] == pytest.approx(300.0)


def test_metrics_head_rows_count_whole_head_blocks():
    """``head_rows`` totals the rows the float head ran, whole HEAD_ROWS
    blocks per flush, beside the images served."""
    R = execute.HEAD_ROWS
    m = ServeMetrics(buckets=(1, 4, R, 2 * R))
    for bucket, n_real in ((1, 1), (4, 3), (R, R), (R, 40), (2 * R, R + 6)):
        m.record_flush(bucket, n_real, batch_s=0.01, latencies_s=[0.01] * n_real)
    tot = m.snapshot()["totals"]
    assert tot["images"] == 1 + 3 + R + 40 + R + 6
    assert tot["head_rows"] == R + R + R + R + 2 * R
    assert execute.head_rows(0) == 0 and execute.head_rows(R + 1) == 2 * R


def test_metrics_busy_time_counts_overlapping_flushes_once():
    """The worker double-buffers: batch k+1 is staged inside batch k's
    [t0, t1], so busy time is the union of the flushes' intervals."""
    clk = FakeClock()
    m = ServeMetrics(buckets=(4,))
    clk.sleep(1.0)
    t0_a = clk()
    clk.sleep(0.6)
    t0_b = clk()            # batch b staged while batch a computes
    clk.sleep(0.4)
    m.record_flush(4, 4, t0=t0_a, t1=clk(), latencies_s=[0.1] * 4)
    clk.sleep(0.5)
    m.record_flush(4, 4, t0=t0_b, t1=clk(), latencies_s=[0.1] * 4)
    clk.sleep(1.0)
    m.record_flush(4, 4, t0=clk(), t1=clk() + 0.25, latencies_s=[0.1] * 4)
    tot = m.snapshot()["totals"]
    assert tot["busy_s"] == pytest.approx(1.5 + 0.25)   # not 1.0 + 0.9 + 0.25
    m.record_flush(4, 1, batch_s=0.125, latencies_s=[0.1])
    assert m.snapshot()["totals"]["busy_s"] == pytest.approx(1.875)
    with pytest.raises(ValueError, match="batch_s or t0 and t1"):
        m.record_flush(4, 1, t0=0.0, latencies_s=[0.1])


class TickClock(FakeClock):
    """A fake clock that advances ``dt`` on every read."""

    def __init__(self, dt: float = 1.0):
        super().__init__()
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def test_flush_phases_and_queue_waits_follow_the_injected_clock():
    """Each host phase of a flush is bounded by two consecutive clock
    reads, so on a clock that ticks once per read every phase takes one
    tick and a flush six (the read that starts hand-off belongs to no
    phase); each request's queue wait runs from admission to the poll
    that took it, and its batch number is its flush's."""
    clk = TickClock(dt=1.0)
    srv = _float_server(buckets=(1, 4), clock=clk, sleep=clk.sleep,
                        max_delay_ms=10.0)
    metrics = srv.run_stream(_stream(n=10, process="bursts",
                                     burst_sizes=(1, 4), gap_s=0.1))
    reqs = metrics.requests
    assert all(r.status == "served" for r in reqs)
    snap = metrics.snapshot()
    flushes = snap["totals"]["flushes"]
    for p in ("pad", "stage", "launch", "block", "deliver"):
        assert metrics.phase_s[p] == [1.0] * flushes
        assert snap["phases"][p] == {"sum_s": float(flushes), "p50_ms": 1e3}
    assert snap["totals"]["busy_s"] == pytest.approx(6.0 * flushes)
    assert metrics.queue_wait_s == [r.t_taken - r.t_submit for r in reqs]
    assert all(w >= 1.0 for w in metrics.queue_wait_s)
    # batches are numbered in flush order; a batch's requests were taken
    # by one poll and are consecutive in admission order
    batches = [r.batch for r in reqs]
    assert batches == sorted(batches)
    assert sorted(set(batches)) == list(range(flushes))
    assert srv.batcher.batches == flushes
    for b in set(batches):
        assert len({r.t_taken for r in reqs if r.batch == b}) == 1


def test_metrics_admission_counters():
    m = ServeMetrics(buckets=(1,))
    for _ in range(5):
        m.record_submit()
    m.record_shed()
    m.record_expired(2)
    m.record_overlap()
    tot = m.snapshot()["totals"]
    assert tot["submitted"] == 5 and tot["shed"] == 1
    assert tot["expired"] == 2 and tot["overlapped"] == 1


def test_metrics_write_stamps_schema_header(tmp_path):
    """Every serve JSON artifact carries schema_version + the same
    backend/device_kind header the BENCH artifacts do, from ONE writer
    (stamp_payload) — compare.py machine-scopes without sniffing."""
    import json

    from repro.serve.metrics import SCHEMA_VERSION

    m = ServeMetrics(buckets=(1,))
    m.record_flush(1, 1, batch_s=0.001, latencies_s=[0.001])
    path = tmp_path / "metrics.json"
    payload = m.write(str(path), extra={"arch": "vgg16-smoke"})
    on_disk = json.load(open(path))
    assert on_disk == payload
    assert on_disk["arch"] == "vgg16-smoke"
    assert on_disk["schema_version"] == SCHEMA_VERSION
    assert on_disk["backend"] == jax.default_backend()
    assert on_disk["device_kind"] == jax.devices()[0].device_kind
    assert on_disk["metrics"]["per_bucket"]["1"]["images"] == 1
    # the bench writer shares the same header rule
    bench = stamp_payload({"section": "serve", "records": []})
    assert bench["schema_version"] == SCHEMA_VERSION
    assert bench["backend"] == on_disk["backend"]


def test_request_handle_defaults():
    r = Request(0, "x", 0.0)
    assert r.status == "pending" and not r.done.is_set()
    assert r.deadline_s is None


def test_serve_cnn_check_fails_when_executables_raise(monkeypatch, capsys,
                                                      tmp_path):
    """``serve_cnn --check`` with no fault plan armed: a run whose every
    batch fails (each ending in terminal ``failed``, so conservation
    still holds) must exit non-zero, not pass."""
    from repro.launch import serve_cnn

    def broken(self, bucket, images):
        raise RuntimeError("executable failed")

    monkeypatch.setattr(serve_cnn, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(ServeEngine, "run_bucket", broken)
    with pytest.raises(SystemExit) as exc:
        serve_cnn.main(["--arch", "vgg16", "--smoke", "--buckets", "1,4",
                        "--requests", "8", "--check",
                        "--out", str(tmp_path / "m.json")])
    assert exc.value.code == 1
    err = capsys.readouterr().err
    assert "8 requests failed with no fault plan armed" in err
    assert "served 0 != submitted 8" in err
