"""Open-loop Poisson arrivals at the fixed rate the mix names.

Independent users: each request is sent when it is due, whatever the
server is doing.  Every seed gets the same set of inter-arrival gaps
(the quantiles of the exponential distribution at the mix's rate) in a
seed-drawn order, and the same images in a seed-drawn order, so seeds
change the arrangement and not the amount of work.

A request is timed from the moment it was due to the moment the harness
sees its result; one that fails, is shed or expires counts as slower
than every served one.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from bench.harness import RunFailed
from bench.serving import ServingDriver


def schedule(rate_hz: float, seconds: float, seed: int):
    """Due times (seconds from the window's start) of the requests."""
    n = int(round(rate_hz * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_hz
    np.random.default_rng(seed).shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return due[due < seconds]


def nearest_rank(xs: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank over all of ``xs``."""
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def slower_than_all(latencies: Sequence[Optional[float]], seconds: float) -> List[float]:
    """Latencies with every missing one (failed, shed, expired) made
    slower than every served one."""
    served = [x for x in latencies if x is not None]
    worst = (max(served) if served else 0.0) + seconds
    return [worst if x is None else x for x in latencies]


class Driver(ServingDriver):
    def window(self, seconds: float) -> None:
        run = self.run
        due = schedule(float(run.mix["rate_hz"]), seconds, run.seed)
        order = np.random.default_rng(run.seed + 1).permutation(len(due)) % len(self.images)
        sent: "queue.Queue" = queue.Queue()
        seen = {}

        def observe():
            # the batcher is first in, first out: wait for each in turn
            while (item := sent.get()) is not None:
                i, r = item
                r.done.wait()
                seen[i] = time.perf_counter()

        observer = threading.Thread(target=observe, name="bench-observer", daemon=True)
        observer.start()
        busy0 = self.server.metrics.snapshot()["totals"]["busy_s"]
        reqs, late = [], []
        t0 = time.perf_counter()
        for i, d in enumerate(due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late.append(time.perf_counter() - (t0 + d))
            r = self.server.submit(self.images[order[i]])
            reqs.append(r)
            sent.put((i, r))
        sent.put(None)
        observer.join(120.0)
        if observer.is_alive():
            raise RunFailed("requests still unfinished 120 s after the window")
        self.finish_window()
        served = [i for i, r in enumerate(reqs) if r.status == "served"]
        lat = slower_than_all(
            [seen[i] - (t0 + due[i]) if r.status == "served" else None for i, r in enumerate(reqs)],
            seconds,
        )
        run.obs["late_s"] = late
        run.obs["served"] = len(served)
        run.obs["batch_s"] = self.server.metrics.snapshot()["totals"]["busy_s"] - busy0
        run.e2e["serve_p95_ms"] = 1e3 * nearest_rank(lat, 95)
        run.e2e["serve_p50_ms"] = 1e3 * nearest_rank(lat, 50)
        self.attempted = len(reqs)
        self.failed = len(reqs) - len(served)
        self.results = {i: reqs[i].result for i in served}
        self.image_of = {i: int(order[i]) for i in served}
