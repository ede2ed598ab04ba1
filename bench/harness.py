"""Finds a cell's configuration, traffic mix, system module and metric
readers by the names in ``BENCHMARK.json``, so that a new configuration,
mix or metric is new files and new entries, and no edit.

- configuration: the file the ``configs`` entry names (``bench/configs/``);
  its ``system`` key names the module ``bench/systems/<system>.py``;
- traffic mix: ``bench/traffic/<traffic>.json``, parameters only; its
  ``loop`` key names the module ``bench/traffic/loops/<loop>.py``, whose
  ``drive(mix, system, seconds)`` calls the system's operations (``join``;
  ``draw`` and ``send``) and returns one record per operation;
- metric: ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  value, or None where the run holds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Run:
    """What one run of a cell knows; metric readers read it."""

    cell: Cell
    seed: int
    seconds: float
    peak: dict | None = None  # the device kind's row of peaks.json
    setup_s: float = 0.0
    executables: int = 0  # programs JAX compiled or loaded from its cache so far
    window_s: float = 0.0  # host clock, first operation's start to last one's end
    records: dict[str, Any] = dataclasses.field(default_factory=dict)
    reduction: Any = None  # trace.Reduction of a traced run


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def _load(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    return _load(root / "bench" / "metrics" / f"{name}.py").read


def loop(name: str, root: Path = ROOT) -> ModuleType:
    return _load(root / "bench" / "traffic" / "loops" / f"{name}.py")


def system(name: str, root: Path = ROOT) -> ModuleType:
    return _load(root / "bench" / "systems" / f"{name}.py")


def peaks(root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "peaks.json").read_text())


def read_metrics(run: Run, metrics: list[dict], root: Path = ROOT) -> dict[str, dict]:
    """Each metric's value with its unit; a metric whose reader finds
    nothing is left out."""
    out = {}
    for m in metrics:
        value = reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
