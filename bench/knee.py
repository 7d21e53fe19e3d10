"""Sweep the offered rate of an open-loop serving cell to find its knee.

    python3 bench/knee.py --workload <cell> --rates 100,200,300 --seconds 8

Runs the cell's window at each rate, one server per rate in one process,
and prints one JSON line per rate: the tail and median latency, the
completed rate and how late the generator ran.  The knee is the highest
rate whose completed rate keeps up with the offered one and whose tail
has not yet run away; a cell offers a fixed share of it.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import argparse

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.spec import Spec
    from bench.traffic.open_poisson import nearest_rank

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    spec = Spec()
    harness.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        run = harness.make_run(spec, args.workload, args.seed, args.seconds)
        run.mix = dict(run.mix, rate_hz=rate)
        harness.find_chip(run)
        drv = spec.driver(run.mix["driver"]).Driver(run)
        drv.setup()
        t0 = time.perf_counter()
        drv.window(run.seconds)
        wall = time.perf_counter() - t0
        drv.release()
        line = {
            "rate_hz": rate,
            "p50_ms": run.e2e["serve_p50_ms"],
            "p95_ms": run.e2e["serve_p95_ms"],
            "completed_per_s": run.obs["served"] / wall,
            "late_p95_ms": 1e3 * nearest_rank(run.obs["late_s"], 95),
            "flushes": run.obs["flushes"],
            "failed": drv.failed,
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
