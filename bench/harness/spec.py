"""Everything a run needs, found by name from ``BENCHMARK.json``.

- the cell: ``workloads[].name``;
- its configuration: ``configs[].file`` (JSON), whose ``reference`` names
  ``bench/reference/<reference>.py``;
- its traffic mix: ``bench/traffic/<traffic>.json``;
- its metrics: every ``end_to_end`` and ``per_layer`` entry that lists
  the cell under ``workloads`` (or has no such key), each read by
  ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List


class SpecError(Exception):
    """The benchmark's files do not describe the requested cell."""


@dataclass(frozen=True)
class CellSpec:
    root: Path
    workload: Dict
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, cell: str) -> CellSpec:
    root = Path(root)
    try:
        bench = json.loads((root / "BENCHMARK.json").read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read BENCHMARK.json: {e}") from None
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise SpecError(f"no workload {cell!r}; known: {sorted(work)}")
    workload = work[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[workload["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic_file = bench_dir(root) / "traffic" / f"{workload['traffic']}.json"
    try:
        traffic = json.loads(traffic_file.read_text())
    except OSError:
        raise SpecError(f"no traffic file {traffic_file}") from None
    return CellSpec(
        root=root, workload=workload, config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, cell)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, cell)])


def _load(path: Path) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(root: Path, name: str) -> ModuleType:
    """``bench/metrics/<name>.py``: its ``read(ctx)`` returns the value,
    or ``None`` where the run gave it nothing to read."""
    return _load(bench_dir(root) / "metrics" / f"{name}.py")


def reference(root: Path, name: str) -> ModuleType:
    """``bench/reference/<name>.py``: ``compare`` and ``control``."""
    return _load(bench_dir(root) / "reference" / f"{name}.py")
