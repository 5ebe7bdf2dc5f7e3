"""Resolve a benchmark cell from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

* ``configs/<config>.json``: the model and mechanism settings as run; its
  plain reference is ``configs/<config>.py`` beside it;
* ``traffic/<traffic>.json``: the federation protocol and dropout of a mix;
* ``limits/<workload>.json``: the limit of each number that decides
  ``correct`` in that cell;
* ``metrics/<metric>.py``: the reader of one per-layer metric.

A cell added to ``BENCHMARK.json`` together with its data files runs with no
edit to any code here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it resolves to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    reference: ModuleType
    end_to_end: tuple      # metric entries that this cell reports
    per_layer: tuple


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by path (metric readers, model
    references), without touching ``sys.path``."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench_file: Path = CHECKOUT / "BENCHMARK.json",
            root: Path = HERE) -> Cell:
    """The cell named ``workload``; raises KeyError for an unknown name and
    FileNotFoundError for a file that the cell needs and that is missing."""
    bench = _load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config_file = bench_file.parent / entry["file"]
    config = _load_json(config_file)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=_load_json(root / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(root / "limits" / f"{workload}.json"),
        reference=load_module(config_file.with_suffix(".py")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, workload)),
    )


def metric_reader(name: str) -> ModuleType:
    """The reader module of per-layer metric ``name``
    (``metrics/<name>.py``, which defines ``read(view)``)."""
    return load_module(HERE / "metrics" / f"{name}.py")
