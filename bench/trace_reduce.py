"""Reduce a ``jax.profiler`` trace of the window to device numbers.

The profiler writes ``plugins/profile/<time>/<host>.xplane.pb``; each
TPU is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one
event per operation the chip ran, with its start and duration in
nanoseconds on the host's clock.  From those this module takes:

- busy time: the union of the operations' intervals on each chip,
  averaged over the chips, and the window the trace covers;
- time per operation name, and the Pallas kernels' time by kernel;
- collective time (all-reduce, all-gather, reduce-scatter, ...) and the
  part of it during which nothing else ran on that chip;
- the longest idle gaps, each labelled with the harness span (a
  ``bench.<name>`` annotation on a host thread) that was open at the
  time.

The window is the harness's ``bench.window`` annotation, on the same
clock as the device's operations.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I
)
OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
SHAPE = re.compile(r"[a-z0-9]+\[[^\]]*\]")


def short_name(text: str) -> str:
    """``%name type opcode`` of an operation whose trace name is its HLO
    text (``%name = type{layout} opcode(operands), ...``); other names
    as they are."""
    if " = " not in text:
        return text[:120]
    name, rest = text.split(" = ", 1)
    op = OPCODE.search(rest)
    shape = SHAPE.match(rest)
    return f"{name} {shape.group(0) if shape else 'tuple'} {op.group(1) if op else ''}".strip()


def is_collective(op: "Op") -> bool:
    """An all-reduce, all-gather, reduce-scatter, collective-permute or
    all-to-all, by the operation's own name and opcode: an operation that
    only takes a collective's result as an operand is not one."""
    return bool(COLLECTIVE.search(short_name(op.name)))


@dataclass
class Op:
    #: on a TPU, the operation's HLO text
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a: Sequence[Tuple[int, int]], s: int, e: int) -> int:
    """Nanoseconds of [s, e) covered by the sorted disjoint intervals ``a``."""
    tot = 0
    for x, y in a:
        if y <= s:
            continue
        if x >= e:
            break
        tot += min(y, e) - max(x, s)
    return tot


@dataclass
class Reduced:
    """The device side of one traced window."""

    #: chip index -> that chip's operations, in start order
    ops: Dict[int, List[Op]] = field(default_factory=dict)
    #: trace clock (ns) of the window's start and end
    t0_ns: int = 0
    t1_ns: int = 0
    #: the harness's host spans: (name, start_ns, end_ns)
    spans: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def _busy(self, chip: int) -> List[Tuple[int, int]]:
        return _union((o.start_ns, o.end_ns) for o in self.ops[chip])

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        tot = sum(sum(e - s for s, e in self._busy(c)) for c in self.ops)
        return tot / len(self.ops) / 1e9

    def seconds(self, match) -> float:
        """Device seconds of the operations ``match(op)`` accepts, summed
        over chips and averaged over them."""
        if not self.ops:
            return 0.0
        tot = sum(o.dur_ns for ops in self.ops.values() for o in ops if match(o))
        return tot / len(self.ops) / 1e9

    def count(self, match) -> float:
        """Operations ``match`` accepts, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(1 for ops in self.ops.values() for o in ops if match(o)) / len(self.ops)

    def by_name(self) -> List[Tuple[str, float]]:
        """(op name, device seconds averaged over chips), longest first."""
        tot: Dict[str, int] = {}
        for ops in self.ops.values():
            for o in ops:
                tot[o.name] = tot.get(o.name, 0) + o.dur_ns
        n = max(len(self.ops), 1)
        return sorted(((k, v / n / 1e9) for k, v in tot.items()), key=lambda kv: -kv[1])

    def collective_exposed_s(self) -> float:
        """Collective time during which no other operation ran on that
        chip, averaged over the chips."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            other = _union((o.start_ns, o.end_ns) for o in ops if not is_collective(o))
            for o in ops:
                if is_collective(o):
                    tot += o.dur_ns - _overlap(other, o.start_ns, o.end_ns)
        return tot / len(self.ops) / 1e9

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle intervals of chip 0 inside the window."""
        if not self.ops:
            return [(self.t0_ns, self.t1_ns)]
        busy = self._busy(min(self.ops))
        out, t = [], self.t0_ns
        for s, e in busy:
            if s > t:
                out.append((t, min(s, self.t1_ns)))
            t = max(t, e)
        if t < self.t1_ns:
            out.append((t, self.t1_ns))
        return [(s, e) for s, e in out if e > s]

    def label(self, t_ns: int) -> str:
        """The innermost harness span open at ``t_ns``."""
        best = None
        for name, s, e in self.spans:
            if s <= t_ns < e and (best is None or s >= best[1]):
                best = (name, s)
        return best[0] if best else "outside the harness"

    def breakdown(self) -> dict:
        """The ten longest device operations and idle gaps; each gap is
        named by the harness span open at its start."""
        ops = [[short_name(name), secs] for name, secs in self.by_name()[:10]]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        label = self.label

        return {
            "device_ops": ops,
            "idle_gaps": [[label(s), (e - s) / 1e9] for s, e in gaps],
        }


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _host_spans(data) -> List[Tuple[str, int, int]]:
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return sorted(out, key=lambda x: x[1])


def reduce_file(path: str) -> Reduced:
    """Read one ``.xplane.pb``; keep the TPU operations that overlap the
    harness's window (the whole trace where it has none)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    red = Reduced(spans=_host_spans(data))
    win = [(s, e) for n, s, e in red.spans if n == WINDOW]
    t0_ns, t1_ns = win[0] if win else (None, None)
    lo, hi = None, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops: List[Op] = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                d = int(ev.duration_ns)
                if t0_ns is not None and (s + d <= t0_ns or s >= t1_ns):
                    continue
                ops.append(Op(ev.name, s, d))
        ops.sort(key=lambda o: o.start_ns)
        red.ops[int(m.group(1))] = ops
        if ops:
            lo = min(lo if lo is not None else ops[0].start_ns, ops[0].start_ns)
            hi = max(hi or 0, max(o.end_ns for o in ops))
    red.t0_ns = t0_ns if t0_ns is not None else (lo or 0)
    red.t1_ns = t1_ns if t1_ns is not None else (hi or 0)
    return red


def reduce_dir(trace_dir: str, chips: int) -> Reduced:
    red = reduce_file(find_xplane(trace_dir))
    keep = sorted(red.ops)[:chips]
    red.ops = {c: red.ops[c] for c in keep}
    return red
