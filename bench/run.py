"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with the chips the cell asks for; anywhere else it exits
non-zero and prints no result.  The cells, configurations, traffic mixes
and metrics are files under ``bench/``, named by ``BENCHMARK.json``.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"[bench] no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import main as run_main

    return run_main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
