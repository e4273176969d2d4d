"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

A cell names a configuration and a traffic mix; the metrics it reports
are the ``BENCHMARK.json`` metrics whose ``workloads`` list it (or that
have no such list). Each piece is found by name, so a later PR adds a
cell, a mix, a configuration or a metric by adding files and entries:

- configuration: the ``file`` of its ``configs`` entry (JSON);
- traffic mix: ``benchmark/traffic/<traffic>.json``, read by the
  generator it names (``generators/``), whose calls name op files
  (``ops/``);
- metric: ``benchmark/metrics/<name>.py``, or, where there is none, the
  file of the name's stem before its first dot
  (``metrics/idle_share.py`` serves ``idle_share.small`` and
  ``idle_share.large``): a reader with ``read(run) -> float | None``
  (``None``: nothing to read here, and the metric is left out of the
  line).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from benchmark import generators, load_file, ops

HERE = os.path.dirname(os.path.abspath(__file__))
#: the checkout's root: the directory that holds ``BENCHMARK.json``
ROOT = os.path.dirname(HERE)


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclass
class Metric:
    name: str
    unit: str
    reader: Callable[[dict], Optional[float]]
    moves: Optional[str] = None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: the checkout the cell's files were found in
    root: str = ROOT
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"{path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_reader(root: str, name: str) -> Callable[[dict], Optional[float]]:
    """The ``read`` function of ``benchmark/metrics/<name>.py``, else of
    the file of the name's stem."""
    try:
        mod = load_file(root, "metrics", name)
    except ValueError:
        try:
            mod = load_file(root, "metrics", name.split(".")[0])
        except ValueError as e:
            raise SpecError(f"metric {name!r}: {e}") from None
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{mod.__file__} defines no read(run)")
    return mod.read


def _metrics_for(root: str, entries: List[dict], cell: str) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        out.append(Metric(name=m["name"], unit=m["unit"],
                          reader=load_reader(root, m["name"]),
                          moves=m.get("moves")))
    return out


def resolve_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "benchmark", "traffic",
                                      f"{w['traffic']}.json"))
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, root=root,
                end_to_end=_metrics_for(root, bench.get("end_to_end", []),
                                        name),
                per_layer=_metrics_for(root, bench.get("per_layer", []),
                                       name))
    if int(config.get("chips", cell.chips)) != cell.chips:
        raise SpecError(f"workload {name!r} asks for {cell.chips} chips, "
                        f"its config for {config['chips']}")
    try:
        for op in {p[0] for p in generators.load(traffic, root).pairs(
                traffic)}:
            ops.load(op, root)
    except (KeyError, ValueError) as e:
        raise SpecError(f"workload {name!r}: traffic "
                        f"{w['traffic']!r}: {e}") from e
    return cell


def all_cells(root: str = ROOT) -> Dict[str, Cell]:
    return {w["name"]: resolve_cell(w["name"], root)
            for w in load_benchmark(root).get("workloads", [])}
