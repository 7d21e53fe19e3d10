"""Closed loop: a fixed number of requests outstanding, each completion
sends the next.

Bulk offline work: the caller keeps the server full and waits for
nothing but results.  The images cycle through the pool in a
seed-drawn order.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench.harness import RunFailed
from bench.serving import ServingDriver


class Driver(ServingDriver):
    def window(self, seconds: float) -> None:
        run = self.run
        pool = len(self.images)
        order = np.random.default_rng(run.seed + 1).permutation(pool)
        inflight = collections.deque()
        reqs, done_at = [], {}

        def send():
            i = len(reqs)
            r = self.server.submit(self.images[order[i % pool]])
            reqs.append(r)
            inflight.append((i, r))

        t0 = time.perf_counter()
        end = t0 + seconds
        for _ in range(int(run.mix["outstanding"])):
            send()
        while inflight:
            i, r = inflight[0]
            if not r.done.wait(120.0):
                raise RunFailed(f"request {i} unfinished after 120 s")
            t = time.perf_counter()
            inflight.popleft()
            done_at[i] = t
            if t < end:
                send()
        self.finish_window()
        served = [i for i, r in enumerate(reqs) if r.status == "served"]
        in_window = sum(done_at[i] < end for i in served)
        run.e2e["serve_images_per_s"] = in_window / seconds
        self.attempted = len(reqs)
        self.failed = len(reqs) - len(served)
        self.results = {i: reqs[i].result for i in served}
        self.image_of = {i: int(order[i % pool]) for i in served}
