"""One run of one cell: set-up, a measured window, the correctness check
and the result line.

``run.py`` parses the command line and calls :func:`main`.  The order of
a run is fixed here for every cell:

1. look for the chip (a TPU with as many devices as the cell asks for,
   whose ``device_kind`` is in ``peaks.json``), or fail;
2. the cell's traffic driver builds the program and warms every shape
   the window will use (this is ``setup_s``, counted from process start);
3. the window, ``--seconds`` long, under the profiler with ``--trace 1``;
   any trace, lowering or compile inside it fails the run;
4. the device's peak memory is read, the program's state is freed, and
   the traffic driver compares what the window produced with the plain
   reference;
5. end-to-end metrics (``--trace 0``) or the per-layer readers
   (``--trace 1``), then the last line.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

from bench.spec import ROOT, Spec

#: JAX's own monitoring events for tracing, lowering and compiling.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def checks_from(run: "Run", readings: Dict[str, float]) -> List["Check"]:
    """The readings the cell gives a limit, each beside it; every reading
    is logged.  A cell whose limits are all unset cannot be judged."""
    for name, value in readings.items():
        log(f"reading {name} {value!r}")
    limits = run.cell["check"]["limits"]
    checks = [Check(n, float(readings[n]), float(v)) for n, v in limits.items() if v is not None]
    if not checks:
        raise RunFailed(f"{run.name}: no compared number has a limit")
    return checks


class RunFailed(RuntimeError):
    """The run cannot give a result: no chip, a layer off its kernel, a
    compile inside the window, or a malformed cell."""


@dataclass
class Check:
    """One number the correctness check compares, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Run:
    """What a run knows; the traffic driver and the metric readers read it."""

    spec: Spec
    name: str
    seed: int
    seconds: float
    trace: bool
    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    peaks: Dict[str, float] = field(default_factory=dict)
    devices: List[Any] = field(default_factory=list)
    #: raw observations of the window, filled by the traffic driver
    obs: Dict[str, Any] = field(default_factory=dict)
    #: end-to-end values, filled by the traffic driver
    e2e: Dict[str, float] = field(default_factory=dict)
    #: the reduced device trace (``trace_reduce.Reduced``), ``--trace 1``
    reduced: Any = None

    def span(self, name: str):
        """A harness span, written into the profiler's trace as
        ``bench.<name>`` when one is being taken."""
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    @property
    def program_seed(self) -> int:
        """The seed handed to the program and the reference: the run's
        seed folded into 31 bits, which every parser and key accepts."""
        return self.seed % (2**31 - 1)


def make_run(spec: Spec, name: str, seed: int, seconds: float, trace: bool = False, **cell_changes) -> Run:
    """The run of cell ``name``, its configuration and traffic mix found
    by name; ``cell_changes`` override keys of the cell's file."""
    cell = dict(spec.cell(name), **cell_changes)
    return Run(
        spec=spec,
        name=name,
        seed=seed,
        seconds=seconds,
        trace=trace,
        cell=cell,
        cfg=spec.config(cell["config"]),
        mix=spec.mix(cell["traffic"]),
    )


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def find_chip(run: Run, platform: str = "tpu") -> None:
    """Fill ``run.devices`` and ``run.peaks``, or raise: the benchmark
    never falls back to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise RunFailed(f"needs a {platform}, JAX found {devs[0].platform}")
    chips = int(run.cell["chips"])
    if len(devs) < chips:
        raise RunFailed(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    run.devices = devs[:chips]
    run.peaks = run.spec.peaks(devs[0].device_kind)


def enable_cache() -> str:
    """The persistent compilation cache at a fixed path in the checkout,
    handed to the program's own switch."""
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.launch.cache import enable_compile_cache

    return enable_compile_cache()


class CompileWatch:
    """Counts JAX traces, lowerings and compiles while armed, and adds up
    their seconds and the compilation cache's hits and misses always
    (what set-up spends where)."""

    def __init__(self):
        import jax

        self.events: List[str] = []
        self.armed = False
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._count)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            key = event.rsplit("/", 1)[-1]
            self.seconds[key] = self.seconds.get(key, 0.0) + duration
            if self.armed:
                self.events.append(event)

    def _count(self, event: str, **kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            key = event.rsplit("/", 1)[-1]
            self.counts[key] = self.counts.get(key, 0) + 1

    def summary(self) -> str:
        parts = [f"{k} {v:.3f} s" for k, v in sorted(self.seconds.items())]
        parts += [f"{k} {v}" for k, v in sorted(self.counts.items())]
        return ", ".join(parts)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def execute(
    run: Run,
    *,
    t_start: float,
    find: Callable[[Run], None] = find_chip,
) -> Dict[str, Any]:
    """Set-up, window, check and metrics for one run; returns the result
    line as a dict (the caller prints it)."""
    find(run)
    watch = CompileWatch()
    drv = run.spec.driver(run.mix["driver"]).Driver(run)
    with run.span("setup"):
        drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; {watch.summary()}")

    counts0 = drv.compile_counts()
    tmp = None
    watch.armed = True
    if run.trace:
        import jax

        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tmp)
    try:
        with run.span("window"):
            drv.window(run.seconds)
    finally:
        if run.trace:
            import jax

            jax.profiler.stop_trace()
        watch.armed = False
    if watch.events or drv.compile_counts() != counts0:
        raise RunFailed(
            f"compiled inside the window: {len(watch.events)} JAX compile "
            f"events, executables {counts0} -> {drv.compile_counts()}"
        )
    mem = memory_peak(run.devices)
    drv.release()
    checks = checks_from(run, drv.read())
    attempted, failed = drv.attempted, drv.failed

    dev0 = run.devices[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": mem,
    }
    out: Dict[str, Any] = {"correct": all(c.ok for c in checks) and failed == 0}
    out["attempted"] = int(attempted)
    out["failed"] = int(failed)
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if run.trace:
        from bench import trace_reduce

        try:
            run.reduced = trace_reduce.reduce_dir(tmp, len(run.devices))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        for m in run.spec.metrics_for(run.name, "per_layer"):
            value = run.spec.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = run.reduced.breakdown()
    else:
        run.e2e["setup_s"] = setup_s
        for m in run.spec.metrics_for(run.name, "end_to_end"):
            if m["name"] not in run.e2e:
                raise RunFailed(f"the traffic driver did not measure {m['name']}")
            metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else str(c.value), "limit": c.limit}
        for c in checks
    }
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}")
    return out


def main(argv: List[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        raise SystemExit("--seconds must be > 0")

    run = make_run(Spec(), args.workload, args.seed, args.seconds, bool(args.trace))
    enable_cache()
    try:
        out = execute(run, t_start=t_start)
    except RunFailed as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0
