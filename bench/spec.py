"""Finding the benchmark's pieces by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under ``bench/``, found by
the name that ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the layer table the cell runs;
- ``workloads/<cell>.json``: configuration, chips, traffic mix, the
  launcher arguments handed to the program's own parser, the
  correctness sample and its limits;
- ``traffic/<mix>.json``: the mix's parameters and the ``driver`` that
  generates it, ``traffic/<driver>.py``;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``reference/<reference>.py``: the plain reference a configuration
  names under ``"reference"``: its weights, forward pass and loss, and
  the check that the program's own entry is the model the file states;
- ``peaks.json``: the chip's published peaks by ``device_kind``.

Adding a cell, a mix, a configuration or a metric is adding files and
``BENCHMARK.json`` entries; nothing here names any of them.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """A benchmark file is missing, malformed or names something unknown."""


def _json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"no such benchmark file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON: {e}") from None


def _module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise SpecError(f"no such benchmark module: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """The benchmark as files: ``BENCHMARK.json`` at ``root`` and the
    per-name files under ``bench_dir``."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "bench")
        self.benchmark = _json(os.path.join(root, "BENCHMARK.json"))
        self._modules: Dict[str, ModuleType] = {}

    def _file(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def cell(self, name: str) -> Dict[str, Any]:
        """The cell's file, checked against its ``BENCHMARK.json`` entry."""
        entry = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not entry:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json")
        cell = _json(self._file("workloads", name + ".json"))
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != entry[0][key]:
                raise SpecError(
                    f"workloads/{name}.json {key}={cell.get(key)!r} but "
                    f"BENCHMARK.json says {entry[0][key]!r}"
                )
        return cell

    def config(self, name: str) -> Dict[str, Any]:
        return _json(self._file("configs", name + ".json"))

    def mix(self, name: str) -> Dict[str, Any]:
        mix = _json(self._file("traffic", name + ".json"))
        if "driver" not in mix:
            raise SpecError(f"traffic/{name}.json names no driver")
        return mix

    def _load(self, path: str, name: str) -> ModuleType:
        if path not in self._modules:
            self._modules[path] = _module(path, name)
        return self._modules[path]

    def driver(self, name: str) -> ModuleType:
        return self._load(self._file("traffic", name + ".py"), f"bench_traffic_{name}")

    def reader(self, metric: str) -> ModuleType:
        path = self._file("metrics", metric + ".py")
        return self._load(path, "bench_metric_" + metric.replace(".", "_"))

    def reference(self, name: str) -> ModuleType:
        """The plain reference module a configuration names: it exposes
        ``init_params(cfg, seed)``, ``forward(cfg, params, images, lane)``,
        ``loss(cfg, params, images, labels, lane)`` and
        ``check_program(cfg, pcfg)``."""
        path = self._file("reference", name + ".py")
        return self._load(path, "bench_reference_" + name)

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = _json(self._file("peaks.json"))["devices"]
        if device_kind not in table:
            raise SpecError(
                f"device_kind {device_kind!r} is not in peaks.json "
                f"(known: {sorted(table)})"
            )
        return table[device_kind]

    def metrics_for(self, cell: str, group: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
        that list it, and those with no ``workloads`` key."""
        return [
            m
            for m in self.benchmark[group]
            if "workloads" not in m or cell in m["workloads"]
        ]
