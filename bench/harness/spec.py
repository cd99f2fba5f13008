"""The benchmark's data, found by name.

``BENCHMARK.json`` lists configurations, cells and metrics. Everything that
belongs to one of them lives in a file of its own under ``bench/``:

- a configuration: ``configs/<config>.json`` (the file named in
  ``BENCHMARK.json``), its sizes as the source publishes them;
- a traffic mix: ``traffic/<traffic>.json``, parameters read by
  ``harness/generator.py`` and naming the driver (``drivers/<driver>.py``)
  that runs the window;
- a cell: ``cells/<cell>.json``, the serving settings of one pairing;
- a metric: ``metrics/<metric>.py``, a reader with ``read(run)``.

A later cell, mix, configuration or metric is added as files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    settings: Dict[str, Any]      # cells/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: pathlib.Path

    @property
    def driver(self) -> str:
        return self.traffic["driver"]

    def metrics(self, traced: bool) -> List[Dict[str, Any]]:
        return self.per_layer if traced else self.end_to_end


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path) -> Cell:
    """The cell ``name`` of the benchmark at ``root`` (the directory that
    holds ``BENCHMARK.json``)."""
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench_dir = root / spec["paths"][0]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load_json(root / configs[w["config"]]["file"]),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        settings=_load_json(bench_dir / "cells" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
    )


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_driver(cell: Cell):
    return _load_module(cell.bench_dir / "drivers" / f"{cell.driver}.py",
                        f"bench_driver_{cell.driver}")


def metric_reader(bench_dir: pathlib.Path,
                  name: str) -> Callable[[Any], Optional[float]]:
    mod = _load_module(bench_dir / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_").replace("-", "_"))
    return mod.read
