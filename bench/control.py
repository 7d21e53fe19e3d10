"""Readings that set a cell's correctness limits, on the chip at the
cell's own size, several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

For each seed: a short run of the cell as the benchmark makes it (the
program's readings), then the same numbers with the plain reference put
in the program's place in a lower precision (the controls: bfloat16 and
int8 for serving, int8 for training) and, for training, with planted
faults: half of each batch left out and, on several chips, the exchange
between them left out.  One JSON line per seed; the table in ``PERF.md``
is made from these lines.
"""

import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    import argparse

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.spec import Spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    spec = Spec()
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.make_run(spec, args.workload, seed, args.seconds)
        harness.find_chip(run)
        drv = spec.driver(run.mix["driver"]).Driver(run)
        drv.setup()
        drv.window(run.seconds)
        drv.release()
        line = {
            "seed": seed,
            "attempted": drv.attempted,
            "failed": drv.failed,
            "program": drv.read(),
        }
        line.update(drv.control())
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
