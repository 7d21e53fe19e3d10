"""Production CNN serving CLI on the shared serving core (DESIGN.md §8).

  PYTHONPATH=src python -m repro.launch.serve_cnn --arch vgg16 --smoke \\
      --buckets 1,4,16 --requests 64 --rate 200 --max-delay-ms 5 \\
      --producers 4 --queue-capacity 32 --overload block

Builds one ``repro.serve.Server`` from a frozen ``ServeConfig``
(``launch.cli.serving_parent`` flags -> ``ServeConfig.from_args``, the
one mapping both serving launchers share).  The server AOT-compiles one
executable per (ModelPlan, batch bucket) up front
(``ModelPlan.executable_for`` -> ``jit().lower().compile()``, so the
request stream cannot retrace), then serves a deterministic synthetic
request stream (``data.pipeline.SyntheticRequestStream``) through
pad-and-bucket admission with deadline flush — single-threaded inline
(``--producers 0``, deterministic) or through ``--producers N`` real
producer threads feeding the dedicated flush worker (double-buffered
host<->device staging; bounded queue + ``--overload`` policy).

Execution flags (``--substrate`` / ``--int8`` / ``--int5`` / ``--tuning``)
come from the shared launcher parent (``launch.cli``) — ``--tuning
cached`` plans each bucket off its batch-specific persisted autotuner
winners; ``--int8`` serves the fused integer datapath off calibrated
per-channel requant pairs (the only batch-shape-independent int8 lane);
``--int5`` serves the same fused datapath off MSR-compressed 5-bit-stored
weights (DESIGN.md §9.3).  ``--check`` (the CI
serve-smoke / serve-stress / chaos-smoke gate) exits non-zero unless
extended request conservation holds (served + shed + expired + failed ==
submitted, no request left pending), no request failed unless a fault
plan is armed, metrics are non-empty, no
executable compiled more than once — and, in the deterministic inline
mode, every bucket flushed at least once; on failure it also dumps the
admission ledger (every request's terminal state + the fault ledger) as
JSON to stderr.

``--faults SPEC`` arms the seeded fault-injection plane (DESIGN.md §11)
and the degradation ladder behind it: injected stage/compile/executable
faults, worker crashes, int5 wire bit-flips, NaN batches, and latency
spikes, recovered by bounded retries, the watchdog, checksummed-weight
restore, and the circuit breaker's lane degradation.
"""

import argparse
import json
import sys

import jax

from repro.configs import CNN_REGISTRY, CNN_SMOKES
from repro.data.pipeline import SyntheticRequestStream
from repro.engine import plan_model
from repro.launch.cache import enable_compile_cache
from repro.launch.cli import (execution_parent, policy_from_args,
                              serve_config_from_args, serving_parent)
from repro.serve import Lane, PackedWire, Server


def make_stream(cfg, args, buckets):
    """The synthetic request stream for one serve run: the bursts process
    cycles the bucket sizes (with gaps past the flush deadline), so every
    bucket flushes at least once — what the CI smoke asserts."""
    return SyntheticRequestStream(
        hw=cfg.input_hw,
        channels=cfg.layers[0].M,
        n_classes=cfg.n_classes,
        n_requests=args.requests,
        rate_hz=args.rate,
        seed=args.seed,
        process=args.arrival,
        burst_sizes=tuple(buckets),
        gap_s=4.0 * args.max_delay_ms / 1e3,
        dtype="uint8" if (args.int8 or getattr(args, "int5", False))
        else "float32",
    )


def build_server(cfg, policy, serve_config, *, seed=0, calib_batch=8):
    """ModelPlan -> params (+ integer quantization/calibration) -> warm
    Server (every bucket executable compiled before the first request).

    The integer datapaths quantize the freshly-initialized float params
    (int8: symmetric per-tensor weights; int5: the MSR-compressed lane,
    DESIGN.md §9.3) and calibrate per-channel requant pairs on a sample
    burst — both requirements of bit-faithful padded-bucket serving.

    With ``--faults`` armed the server also carries its degradation
    ladder (DESIGN.md §11.3): int5 serves off the checksummed
    ``PackedWire`` payload with an int8 fallback lane (calibrated off the
    same float master, so degraded outputs are a native int8 server's);
    int8/float get a substrate sibling (f32exact / oracle — bit-identical
    numerics, throughput-only sacrifice)."""
    plan = plan_model(cfg, policy)
    params = plan.init(jax.random.PRNGKey(seed))
    armed = serve_config.faults is not None
    if serve_config.datapath == "float":
        fallbacks = [Lane("float-oracle", "float", params,
                          substrate="oracle")] if armed else None
        return Server.from_plan(plan, params, serve_config,
                                fallbacks=fallbacks)
    sample = SyntheticRequestStream(
        hw=cfg.input_hw, channels=cfg.layers[0].M, n_classes=cfg.n_classes,
        seed=seed, dtype="uint8").sample_batch(calib_batch)
    if serve_config.datapath == "int5":
        qparams, _ = plan.quantize_int5(params)
        requant = plan.calibrate_requant_int5(qparams, sample)
        fallbacks = wire = None
        if armed:
            wire = PackedWire(cfg, params)
            q8, _ = plan.quantize(params)
            fallbacks = [Lane("int8", "int8", q8,
                              plan.calibrate_requant(q8, sample))]
        return Server.from_plan(plan, qparams, serve_config,
                                requant=requant, fallbacks=fallbacks,
                                wire=wire)
    qparams, _ = plan.quantize(params)
    requant = plan.calibrate_requant(qparams, sample)
    fallbacks = [Lane("int8-f32exact", "int8", qparams, requant,
                      substrate="f32exact")] if armed else None
    return Server.from_plan(plan, qparams, serve_config, requant=requant,
                            fallbacks=fallbacks)


def check_run(server, metrics, n_requests, *, expect_all_buckets) -> list:
    """The --check assertions; returns a list of failure strings.

    Extended conservation (DESIGN.md §11.4) is the invariant that must
    hold in every mode, fault plane armed or not: every submitted
    request ends in exactly one terminal state.  With no fault plan
    armed nothing may fail: a ``failed`` request is a broken executable,
    not a planned fault, so ``failed`` must be 0 — and when the config
    can neither shed nor expire a request, every request must be served.
    Per-bucket
    flush coverage is only deterministic in the inline open loop (the
    bursts stream is sized to the buckets); under ``--producers N`` the
    interleaving decides bucket fills, so that check is skipped.
    """
    fails = []
    tot = metrics.snapshot()["totals"]
    if tot["submitted"] != n_requests:
        fails.append(f"submitted {tot['submitted']} != offered {n_requests}")
    failed = tot.get("failed", 0)
    if tot["images"] + tot["shed"] + tot["expired"] + failed \
            != tot["submitted"]:
        fails.append(
            "conservation violated: served %d + shed %d + expired %d + "
            "failed %d != submitted %d"
            % (tot["images"], tot["shed"], tot["expired"], failed,
               tot["submitted"]))
    cfg = server.config
    if cfg.faults is None:
        if failed:
            fails.append(f"{failed} requests failed with no fault plan armed")
        may_drop = (cfg.overload == "shed"
                    or cfg.request_timeout_ms is not None)
        if not may_drop and tot["images"] != tot["submitted"]:
            fails.append(f"served {tot['images']} != submitted "
                         f"{tot['submitted']}")
    statuses = [r.status for r in metrics.requests]
    if any(s == "pending" for s in statuses):
        fails.append(f"{statuses.count('pending')} requests left pending")
    rids = [r.rid for r in metrics.requests]
    if len(set(rids)) != len(rids):
        fails.append("duplicate request ids")
    for r in metrics.requests:
        if r.status == "served" and r.result is None:
            fails.append(f"request {r.rid} served without a result")
            break
    if expect_all_buckets:
        for b in server.engine.buckets:
            if metrics.flushes(b) < 1:
                fails.append(f"bucket {b} never flushed")
    bad = {k: v for k, v in server.engine.compile_counts.items() if v != 1}
    if bad:
        fails.append(f"executables compiled more than once: {bad}")
    if not metrics.snapshot()["per_bucket"]:
        fails.append("metrics snapshot is empty")
    return fails


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        parents=[execution_parent(arch_choices=CNN_REGISTRY,
                                  arch_default="vgg16"),
                 serving_parent()])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny arch variant (CNN_SMOKES) for CI")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="mean arrival rate (req/s) for poisson/uniform")
    ap.add_argument("--arrival", choices=("poisson", "uniform", "bursts"),
                    default="bursts",
                    help="arrival process (bursts cycles the bucket sizes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/serve/metrics.json")
    ap.add_argument("--check", action="store_true",
                    help="assert request conservation, no failed "
                         "request without a fault plan, compile-once (and "
                         ">=1 flush per bucket in inline mode); exit "
                         "non-zero on failure (CI gate)")
    return ap


def main(argv=None) -> None:
    enable_compile_cache()
    args = build_parser().parse_args(argv)

    policy = policy_from_args(args)
    serve_config = serve_config_from_args(args)
    cfg = (CNN_SMOKES if args.smoke else CNN_REGISTRY)[args.arch]

    server = build_server(cfg, policy, serve_config, seed=args.seed)
    try:
        metrics = server.run_stream(
            make_stream(cfg, args, serve_config.buckets),
            producers=args.producers)
    finally:
        server.close()
    snap = metrics.snapshot()

    extra = {
        "arch": cfg.name,
        "datapath": serve_config.datapath,
        "arrival": args.arrival,
        "requests": args.requests,
        "max_delay_ms": args.max_delay_ms,
        "producers": args.producers,
        "queue_capacity": serve_config.queue_capacity,
        "overload": serve_config.overload,
        "plan": list(server.engine.plan.describe()),
        "executables": dict(server.engine.compile_counts),
    }
    injector = server.engine.injector
    if injector is not None:
        # stamp the chaos schedule + what actually fired, so a degraded
        # run is visible in its artifact (DESIGN.md §11.3)
        extra["faults"] = injector.plan.describe()
        extra["fault_ledger"] = dict(injector.fired)
        extra["lanes"] = [ln.name for ln in server.engine.lanes]
    payload = metrics.write(args.out, extra=extra)

    tot = snap["totals"]
    mode = (f"{args.producers} producers" if args.producers
            else "inline open loop")
    print(f"[serve_cnn] {cfg.name} {serve_config.datapath} "
          f"buckets={list(serve_config.buckets)} ({mode}) "
          f"served {tot['images']}/{tot['submitted']} "
          f"(shed {tot['shed']}, expired {tot['expired']}, "
          f"overlapped {tot['overlapped']}) in {tot.get('wall_s', 0):.3f}s "
          f"({tot.get('images_per_s', 0):.1f} img/s, p99 {tot['p99_ms']:.1f} ms, "
          f"pad waste {tot['pad_waste']:.1%})")
    for b, rec in snap["per_bucket"].items():
        print(f"[serve_cnn]   bucket {b:>3}: {rec['flushes']} flushes, "
              f"{rec['images_per_s']:.1f} img/s, p99 {rec['p99_ms']:.2f} ms")
    print(f"[serve_cnn] wrote {args.out} "
          f"({len(json.dumps(payload))} bytes)")

    if args.check:
        fails = check_run(server, metrics, args.requests,
                          expect_all_buckets=args.producers == 0)
        if fails:
            for f in fails:
                print(f"[serve_cnn] CHECK FAILED: {f}", file=sys.stderr)
            # the admission ledger: every request's terminal state (plus
            # what the fault plane fired), so a CI failure is debuggable
            # from the log alone
            ledger = {
                "fails": fails,
                "totals": tot,
                "requests": [
                    dict({"rid": r.rid, "status": r.status},
                         **({"error": r.error} if r.error else {}))
                    for r in sorted(metrics.requests, key=lambda r: r.rid)
                ],
            }
            if injector is not None:
                ledger["fault_ledger"] = dict(injector.fired)
            json.dump(ledger, sys.stderr, indent=1)
            print(file=sys.stderr)
            sys.exit(1)
        print("[serve_cnn] check OK: request conservation holds, every "
              "executable compiled exactly once"
              + ("" if args.producers else ", every bucket flushed"))


if __name__ == "__main__":
    main()
