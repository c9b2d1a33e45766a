"""What one benchmark run is: the cell, its configuration and its traffic.

Everything is found by name. ``BENCHMARK.json`` at the checkout root
lists the cells (``workloads``); a cell names a configuration, whose file
``BENCHMARK.json`` gives, and a traffic mix, read from
``bench/traffic/<traffic>.json``. The metrics a run reports are the
``end_to_end`` (untraced run) or ``per_layer`` (traced run) entries whose
``workloads`` list names the cell, or that have no such list.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

#: the checkout root: bench/spec.py -> parents[1]
ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """The benchmark's files do not describe the cell asked for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    end_to_end: tuple     # metric entries reported with --trace 0
    per_layer: tuple      # metric entries reported with --trace 1

    def reports(self, metric: str) -> bool:
        return any(m["name"] == metric
                   for m in self.end_to_end + self.per_layer)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT,
              benchmark: dict | None = None) -> Cell:
    """The cell called ``name``, with its configuration and traffic."""
    bench = benchmark if benchmark is not None else load_json(
        root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))
