"""Find a cell's parts by name: ``BENCHMARK.json`` names the cell, and the
cell names a configuration, a traffic mix and the metrics it reports.  Each
part is a file of its own under ``bench/``:

- ``bench/configs/<config>.json``   model sizes, engine settings, mesh;
- ``bench/traffic/<traffic>.json``  parameters the one generator reads;
- ``bench/limits/<workload>.json``  the limit of each number ``correct``
  compares, with the readings it was set from;
- ``bench/metrics/<metric>.py``     a reader with ``read(run) -> float|None``.

Nothing here knows a particular cell, so a later change adds one by adding
files and ``BENCHMARK.json`` entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Optional[Callable[[Dict[str, Any]], Optional[float]]] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py`` (metric names
    hold dots, so the file is loaded by path, not imported by name)."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: Dict[str, Any], workload: str,
             e2e_of_cell: List[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    # a metric without a list is reported wherever the end-to-end metric
    # it moves is
    return metric.get("moves", metric["name"]) in e2e_of_cell


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """Everything one cell of ``<root>/BENCHMARK.json`` needs, or a
    ``KeyError`` naming what is missing."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = _json(root / configs[w["config"]]["file"])
    bench_dir = root / "bench"
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _json(bench_dir / "limits" / f"{workload}.json")
    e2e = [Metric(m["name"], m["unit"], m["better"], m["source"])
           for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m.name for m in e2e]
    per_layer = [Metric(m["name"], m["unit"], m["better"], m["source"],
                        load_reader(m["name"], bench_dir))
                 for m in bench["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), conf, traffic, limits, e2e,
                per_layer)
