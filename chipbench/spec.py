"""Find the pieces of a cell by name.

A cell of `BENCHMARK.json` names a configuration and a traffic mix.  Each
is a data file here: `configs/<config>.json`, `traffic/<mix>.json`, and
`cells/<cell>.json` for what belongs to the pair (its nominal step time
and the limits of its correctness check).  A configuration names its
model family under `program.family`, a module `families/<family>.py`
(the model's sizes, weights, plain forward pass and work counts); a
traffic mix names its driver, `drivers/<driver>.py`.  A per-layer metric
is a reader `metrics/<metric>.py`.  Nothing here needs an edit when a
cell, a configuration or a family is added.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load_json(ROOT / "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict                 # cells/<cell>.json
    end_to_end: List[dict]       # the e2e metrics this cell reports
    per_layer: List[dict]        # the per-layer metrics this cell reports


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    return Cell(
        name=name, chips=w["chips"],
        config=_load_json(ROOT / conf["file"]),
        traffic=_load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        params=_load_json(HERE / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def family(conf: dict) -> ModuleType:
    """The module `families/<family>.py` that the configuration file
    names under `program.family`; a file that names none, or a family
    with no module, is an error."""
    name = conf.get("program", {}).get("family")
    if name is None:
        raise KeyError(f"configuration {conf.get('name')!r} names no "
                       f"program.family")
    if not (HERE / "families" / f"{name}.py").is_file():
        raise KeyError(f"no family {name!r}: chipbench/families/{name}.py "
                       f"is missing")
    return importlib.import_module(f"chipbench.families.{name}")


def metric_reader(name: str) -> ModuleType:
    """The reader `metrics/<name>.py`; it exposes `read(run)`, which
    returns the metric's value or None where the run has nothing to
    read."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = _load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
