"""``BENCHMARK.json`` and the files it names, found by name.

  * a configuration: ``bench/configs/<config>.json``, whose ``generator``
    names ``bench/generators/<generator>.py``;
  * a traffic mix: ``bench/traffic/<traffic>.json``, whose ``driver`` names
    ``bench/drivers/<driver>.py``, a module with a ``Driver`` class;
  * a cell's correctness limits: ``bench/cells/<cell>.json``;
  * a metric: ``bench/metrics/<metric>.py``, holding ``SOURCE`` and
    ``read(run)``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def generator(name: str):
    return load_module(BENCH / "generators" / f"{name}.py",
                       f"bench_generator_{name}")


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def driver(name: str):
    return load_module(BENCH / "drivers" / f"{name}.py",
                       f"bench_driver_{name}").Driver


def cell_path(name: str) -> Path:
    return BENCH / "cells" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def metric_reader(name: str):
    return load_module(metric_path(name),
                       "bench_metric_" + name.replace(".", "_"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict  # correctness limits
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


def resolve(name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics.

    Raises:
      KeyError: no cell of that name in BENCHMARK.json.
    """
    bench = manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name) and m["moves"] in reported]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(config_path(w["config"])),
        traffic=load_json(traffic_path(w["traffic"])),
        spec=load_json(cell_path(name)),
        end_to_end=e2e, per_layer=per_layer,
    )
