"""Serving metrics: per-bucket throughput, latency percentiles, pad waste.

The TrIM paper's 453.6 GOPS peak (PAPER.md §V) is a sustained-load number,
and the companion dataflow paper frames throughput-per-access as the metric
that matters — both only measurable under load.  These are the software
counters that make the reproduction's serving claims concrete: per-bucket
images/sec (real images over the run's wall-clock), request latency
p50/p99 (submit → result handed off), queue depth at flush time, and the
pad-waste fraction the static buckets cost (padded slots / bucket slots).

The flush worker's host phases are counted per flush: ``pad``, ``stage``,
``launch``, ``block`` and ``deliver`` seconds (the ``repro.serve.*``
spans' own boundaries, on the server's clock), and per request the queue
wait from admission to being taken into a batch.  ``busy_s`` is the union
of the flushes' dispatch-to-hand-off intervals: the worker double-buffers,
so batch k's interval holds batch k+1's staging, which a plain sum would
count twice.  ``head_rows`` totals the rows the float lane's FC head ran,
whole ``execute.HEAD_ROWS`` blocks per flush: over ``images`` it is the
head's own padding, on top of the bucket padding of ``pad_waste``.

Snapshots are plain dicts → JSON: ``BENCH_serve.json`` records and the CI
serve-smoke artifact both come from :meth:`ServeMetrics.snapshot`.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.engine.execute import head_rows

#: Schema version stamped on every serve-metrics / BENCH_serve* JSON
#: artifact (``stamp_payload``).  History:
#:   1 — implicit (PR 6): no version field; device stamp ad-hoc per writer.
#:   2 — ``schema_version`` + top-level ``backend``/``device_kind`` header
#:       (same fields the BENCH kernel artifacts carry), admission
#:       counters (submitted/shed/expired/overlapped) in totals.
#:   3 — ``phases`` (per-flush host phase seconds: sum and median),
#:       ``queue_wait_p50_ms``/``queue_wait_p95_ms`` in totals; ``busy_s``
#:       is the union of flush intervals and per-bucket ``images_per_s``
#:       is over ``wall_s`` when it is set.
SCHEMA_VERSION = 3

#: The flush worker's host phases, in order (``repro.serve.<phase>``).
PHASES = ("pad", "stage", "launch", "block", "deliver")


def device_stamp() -> dict:
    """The ``backend``/``device_kind`` pair every serve artifact carries
    (same stamp rule as the BENCH_kernels records and tuned_plans keys)."""
    import jax

    return {"backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind}


def stamp_payload(payload: Optional[dict] = None) -> dict:
    """THE one place serve JSON writers get their header: schema_version +
    backend/device_kind, then the caller's fields.  ``ServeMetrics.write``
    (launcher metrics artifacts) and ``benchmarks/run.py``'s
    BENCH_serve.json writer both build on this, so ``benchmarks/compare``
    can machine-scope serve metrics off the header without sniffing
    records."""
    out: dict = {"schema_version": SCHEMA_VERSION}
    out.update(device_stamp())
    out.update(payload or {})
    return out


@dataclass
class _BucketStats:
    flushes: int = 0
    images: int = 0
    padded: int = 0
    #: summed engine seconds (the rate's denominator when no wall_s is set)
    batch_s: float = 0.0
    latencies_s: List[float] = field(default_factory=list)
    queue_depth_max: int = 0


def _pctile(xs: Sequence[float], q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, dtype=float), q)) if xs else 0.0


def _union_s(intervals: Sequence[Tuple[float, float]]) -> float:
    """Seconds covered by the union of ``[t0, t1]`` intervals."""
    tot, end = 0.0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            tot += t1 - t0
            end = t1
        elif t1 > end:
            tot += t1 - end
            end = t1
    return tot


class ServeMetrics:
    """Accumulates per-bucket flush observations; snapshots to JSON."""

    def __init__(self, buckets: Sequence[int]):
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self._b: Dict[int, _BucketStats] = {b: _BucketStats() for b in self.buckets}
        self.wall_s: Optional[float] = None  # set by the serve loop
        # Admission counters (conservation: submitted == served + shed +
        # expired at drain).  Incremented from producer threads AND the
        # flush worker, so they take the lock — += is not atomic across
        # bytecodes.
        self._lock = threading.Lock()
        self.submitted = 0
        self.shed = 0
        self.expired = 0
        #: flushes whose host->device staging overlapped a prior
        #: in-flight bucket's compute (the double-buffering win).
        self.overlapped = 0
        # Resilience counters (DESIGN.md §11).  Extended conservation:
        # served + shed + expired + failed == submitted.  They surface in
        # snapshot() only when nonzero, so fault-off snapshots stay
        # byte-identical to the fault-plane-free schema.
        self.failed = 0
        self.retried = 0
        self.degraded = 0
        self.worker_restarts = 0
        self.integrity_restored = 0
        #: breaker key -> lane name it degraded to (insertion-ordered).
        self.degraded_lanes: Dict[str, str] = {}
        #: [t0, t1] of each flush that gave them; ``busy_s`` is their
        #: union plus the ``batch_s`` of flushes that gave only that.
        self._intervals: List[Tuple[float, float]] = []
        self._unplaced_s = 0.0
        #: per phase, one entry per flush that timed its phases, in the
        #: order recorded; per request, t_taken - t_submit, in the order
        #: recorded.  A caller slices the window's part by the counts of
        #: an earlier snapshot (totals ``flushes`` and ``images``).
        self.phase_s: Dict[str, List[float]] = {p: [] for p in PHASES}
        self.queue_wait_s: List[float] = []

    def record_failed(self, n: int = 1) -> None:
        with self._lock:
            self.failed += int(n)

    def record_retried(self, n: int = 1) -> None:
        with self._lock:
            self.retried += int(n)

    def record_degraded(self, key: str, to_lane: str) -> None:
        with self._lock:
            self.degraded += 1
            self.degraded_lanes[str(key)] = str(to_lane)

    def record_worker_restart(self) -> None:
        with self._lock:
            self.worker_restarts += 1

    def record_integrity_restored(self, n: int = 1) -> None:
        with self._lock:
            self.integrity_restored += int(n)

    def record_submit(self) -> None:
        with self._lock:
            self.submitted += 1

    def record_shed(self) -> None:
        with self._lock:
            self.shed += 1

    def record_expired(self, n: int = 1) -> None:
        with self._lock:
            self.expired += int(n)

    def record_overlap(self) -> None:
        with self._lock:
            self.overlapped += 1

    def record_flush(
        self,
        bucket: int,
        n_real: int,
        *,
        latencies_s: Sequence[float],
        batch_s: Optional[float] = None,
        t0: Optional[float] = None,
        t1: Optional[float] = None,
        phases_s: Optional[Mapping[str, float]] = None,
        queue_waits_s: Sequence[float] = (),
        queue_depth: int = 0,
    ) -> None:
        """One shipped batch: ``n_real`` requests padded into ``bucket``
        slots, per-request end-to-end latencies, and the queue depth left
        behind at flush time.  Its engine time is ``[t0, t1]`` (dispatch
        to hand-off) or, without them, ``batch_s`` seconds.
        ``phases_s`` maps each of :data:`PHASES` to its seconds and
        ``queue_waits_s`` holds each request's wait to be taken."""
        if batch_s is None:
            if t0 is None or t1 is None:
                raise ValueError("record_flush needs batch_s or t0 and t1")
            batch_s = t1 - t0
        with self._lock:
            st = self._b.setdefault(int(bucket), _BucketStats())
            st.flushes += 1
            st.images += int(n_real)
            st.padded += int(bucket) - int(n_real)
            st.batch_s += float(batch_s)
            st.latencies_s.extend(float(x) for x in latencies_s)
            st.queue_depth_max = max(st.queue_depth_max, int(queue_depth))
            if t0 is not None and t1 is not None:
                self._intervals.append((float(t0), float(t1)))
            else:
                self._unplaced_s += float(batch_s)
            if phases_s is not None:
                for p in PHASES:
                    self.phase_s[p].append(float(phases_s.get(p, 0.0)))
            self.queue_wait_s.extend(float(x) for x in queue_waits_s)

    @property
    def total_images(self) -> int:
        return sum(st.images for st in self._b.values())

    def flushes(self, bucket: int) -> int:
        st = self._b.get(int(bucket))
        return st.flushes if st else 0

    def snapshot(self) -> dict:
        """The full metrics record (what the launchers/benchmarks emit)."""
        per_bucket = {}
        all_lat: List[float] = []
        total_slots = 0
        total_padded = 0
        for b in sorted(self._b):
            st = self._b[b]
            total_slots += st.flushes * b
            total_padded += st.padded
            all_lat.extend(st.latencies_s)
            span_s = self.wall_s or st.batch_s
            per_bucket[str(b)] = {
                "flushes": st.flushes,
                "images": st.images,
                "images_per_s": round(st.images / span_s, 1) if span_s else 0.0,
                "p50_ms": round(_pctile(st.latencies_s, 50) * 1e3, 3),
                "p99_ms": round(_pctile(st.latencies_s, 99) * 1e3, 3),
                "pad_waste": round(st.padded / (st.flushes * b), 4)
                if st.flushes
                else 0.0,
                "queue_depth_max": st.queue_depth_max,
            }
        busy_s = _union_s(self._intervals) + self._unplaced_s
        totals = {
            "images": self.total_images,
            # rows the float lane's head ran (whole blocks): over
            # ``images``, the head's own padding, which pad_waste misses
            "head_rows": sum(st.flushes * head_rows(b) for b, st in self._b.items()),
            "flushes": sum(st.flushes for st in self._b.values()),
            "pad_waste": round(total_padded / total_slots, 4) if total_slots else 0.0,
            "p50_ms": round(_pctile(all_lat, 50) * 1e3, 3),
            "p99_ms": round(_pctile(all_lat, 99) * 1e3, 3),
            "busy_s": round(busy_s, 4),
            "queue_wait_p50_ms": round(_pctile(self.queue_wait_s, 50) * 1e3, 3),
            "queue_wait_p95_ms": round(_pctile(self.queue_wait_s, 95) * 1e3, 3),
            # admission accounting (served == images; conservation:
            # submitted == served + shed + expired once drained)
            "submitted": self.submitted,
            "shed": self.shed,
            "expired": self.expired,
            "overlapped": self.overlapped,
        }
        # Fault-plane ledger: keyed in only when engaged, so a fault-free
        # run's snapshot is byte-identical to the pre-§11 schema.
        for k in ("failed", "retried", "degraded", "worker_restarts",
                  "integrity_restored"):
            v = getattr(self, k)
            if v:
                totals[k] = v
        out_extra = {}
        if self.degraded_lanes:
            out_extra["degraded_lanes"] = dict(self.degraded_lanes)
        if self.wall_s:
            totals["wall_s"] = round(self.wall_s, 4)
            totals["images_per_s"] = round(self.total_images / self.wall_s, 1)
        phases = {
            p: {"sum_s": round(sum(v), 6), "p50_ms": round(_pctile(v, 50) * 1e3, 3)}
            for p, v in self.phase_s.items()
        }
        out = {"buckets": list(self.buckets), "per_bucket": per_bucket,
               "totals": totals, "phases": phases}
        out.update(out_extra)
        return out

    def write(self, path: str, extra: Optional[dict] = None) -> dict:
        """Write ``snapshot()`` (plus ``extra`` stamp fields) as JSON,
        under the serve schema header (``stamp_payload``: schema_version +
        backend/device_kind — callers no longer stamp those by hand)."""
        payload = stamp_payload(extra)
        payload["metrics"] = self.snapshot()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        return payload
