"""Finding a cell's files by the names ``BENCHMARK.json`` gives them.

A later change adds a configuration, a traffic mix, a per-layer metric or a
cell by adding files and entries; nothing here names one of them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List, Optional

#: the directory this file is in; ``root`` below is its parent (the checkout)
BENCH_DIR = pathlib.Path(__file__).resolve().parent


class SpecError(Exception):
    """A name, file or value the benchmark needs is missing or wrong."""


def _load_json(path: pathlib.Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import one file by path, under a name of its own."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    root: pathlib.Path
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # the traffic file's contents
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]

    @property
    def bench_dir(self) -> pathlib.Path:
        return self.root / "bench"

    def reference(self) -> ModuleType:
        ref = self.config["reference"]
        return load_module(self.bench_dir / "models" / f"{ref}.py",
                           f"bench_ref_{ref}")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))


def _reported_in(metric: Dict[str, Any], workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def load_cell(workload: str, root: Optional[pathlib.Path] = None) -> Cell:
    root = pathlib.Path(root) if root is not None else BENCH_DIR.parent
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names no known config")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{workload}.json")
    per_layer = [m for m in bench["per_layer"] if _reported_in(m, workload)]
    e2e = [m for m in bench["end_to_end"] if _reported_in(m, workload)]
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer, limits=limits["limits"])


def peaks(device_kind: str, root: Optional[pathlib.Path] = None
          ) -> Dict[str, float]:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip; an unknown kind is an
    error, never a default."""
    root = pathlib.Path(root) if root is not None else BENCH_DIR.parent
    table = _load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device_kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
