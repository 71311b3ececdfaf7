"""Resolve a cell of ``BENCHMARK.json`` into its configuration, traffic and
metric readers, each found by name in a file of its own:

- ``configs[*].file``                      the configuration (graph, engine);
- ``perfbench/traffic/<traffic>.json``     the traffic mix's parameters;
- ``perfbench/metrics/<metric name>.py``   a reader, ``read(run) -> float | None``.

A later cell, configuration or metric is added with files and entries
only; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: object  # callable(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def find(root: Path, kind: str, filename: str) -> Path:
    """``perfbench/<kind>/<filename>`` under ``root``, else the shipped one
    (a test's own tree adds its files and reuses the rest)."""
    for base in (root, ROOT):
        path = base / "perfbench" / kind / filename
        if path.is_file():
            return path
    raise FileNotFoundError(f"no perfbench/{kind}/{filename} under {root}")


def load_reader(root: Path, name: str):
    """The ``read`` function of ``perfbench/metrics/<name>.py``."""
    path = find(root, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str, e2e_cells: dict[str, set]) -> bool:
    """A metric is reported in the cells it lists; without a list, in every
    cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, in every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or cell in e2e_cells.get(moves, set())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(find(root, "traffic", f"{w['traffic']}.json").read_text())
    all_cells = [c["name"] for c in bench["workloads"]]
    e2e_cells = {m["name"]: {c for c in all_cells if _applies(m, c, {})}
                 for m in bench["end_to_end"]}

    def metrics(group):
        return tuple(
            Metric(m["name"], m["unit"], load_reader(root, m["name"]))
            for m in bench[group] if _applies(m, name, e2e_cells))

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"))
