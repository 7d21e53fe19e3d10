"""Collective-traffic accounting from compiled (post-SPMD) HLO text.

``cost_analysis()`` does not expose collective bytes, so the roofline's
collective term is derived here: every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op is matched and its
per-device wire bytes estimated with the standard ring model:

- all-reduce:          2 x operand bytes   (reduce-scatter + all-gather)
- all-gather:          result bytes        (each device receives ~(n-1)/n)
- reduce-scatter:      operand bytes
- all-to-all:          operand bytes
- collective-permute:  operand bytes

Shapes in compiled HLO are already per-device (post-partitioning), so the
sums are per-device wire bytes per step. Async pairs (-start/-done) are
counted once via the -start op.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")

_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute")

# '%name = <result> <op>(<operands>)'
_LINE_RE = re.compile(
    r"=\s*(?P<result>.*?)\s+"
    r"(?P<op>" + "|".join(_OPS) + r")(?P<async>-start)?\("
    r"(?P<operands>[^)]*)\)")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-op-type {bytes, count} from compiled HLO text (per device)."""
    stats: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"bytes": 0.0, "count": 0})
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        # skip the -done halves of async pairs (the -start carries shapes)
        if f"{op}-done" in line:
            continue
        if op == "all-gather":
            nbytes = _shape_bytes(m.group("result"))
        else:
            nbytes = _shape_bytes(m.group("operands"))
        if op == "all-reduce":
            nbytes *= 2
        stats[op]["bytes"] += nbytes
        stats[op]["count"] += 1
    return dict(stats)


def total_collective_bytes(hlo_text: str) -> float:
    return sum(v["bytes"] for v in collective_stats(hlo_text).values())


def hbm_bytes_estimate(memory_analysis) -> Dict[str, float]:
    """Pull the useful fields out of compiled.memory_analysis()."""
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes", "peak_memory_in_bytes"):
        val = getattr(memory_analysis, field, None)
        if val is not None:
            out[field] = float(val)
    return out
