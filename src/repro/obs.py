"""Named host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>.<phase>`` (``serve.pad``, ``train.sync``, ...), tagged
with the id of the batch or step it belongs to; the profiler stores the
tags as the event's stats (``batch=3, bucket=64``).  A span is written
only while a profiler runs (``jax.profiler.trace``); otherwise it costs
one check, about a microsecond.  The trace puts it on the clock of the
device's operations, so a gap in the device's work can be put down to
the host phase that was running then.

:class:`Laps` also times back-to-back phases on the caller's clock: the
clock read that ends one phase starts the next, so the span and the
seconds of a phase cover the same work.  The spans in the program, and
what reads them, are listed in PERF.md §3.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The span ``repro.<name>``, tagged with ``meta``."""
    return jax.profiler.TraceAnnotation("repro." + name, **meta)


class Laps:
    """Consecutive phases of one batch or step of ``layer``.

    ``with laps("pad"): ...`` runs the body inside the span
    ``repro.<layer>.pad`` and adds the clock's advance since the previous
    boundary to ``seconds["pad"]``; a phase that raises adds nothing.
    """

    def __init__(self, layer: str, clock: Callable[[], float], **meta):
        self.layer = layer
        self.meta = meta
        self._clock = clock
        #: the last boundary's clock reading (at first, construction)
        self.t = clock()
        self.seconds: Dict[str, float] = {}

    def restart(self) -> None:
        """Start the next phase now: the time since the last boundary
        belongs to no phase."""
        self.t = self._clock()

    @contextlib.contextmanager
    def __call__(self, phase: str) -> Iterator[None]:
        with span(f"{self.layer}.{phase}", **self.meta):
            yield
        t = self._clock()
        self.seconds[phase] = self.seconds.get(phase, 0.0) + (t - self.t)
        self.t = t
