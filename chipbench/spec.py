"""Resolve a cell of BENCHMARK.json into the files that make it up.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name BENCHMARK.json gives:

    configs[].file                  the configuration's sizes (JSON)
    makers/<config["maker"]>.py     builds a configuration's model
    traffic/<traffic>.json          a traffic mix's parameters
    drivers/<traffic["driver"]>.py  drives the measured window
    metrics/<per_layer name>.py     reads one per-layer metric; where
                                    there is none, metrics/<family>.py,
                                    the name up to its first '.', reads
                                    every metric of that family

A cell added later brings its files and edits none of these.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(ValueError):
    """BENCHMARK.json names something that is not there."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    config_path: Path
    traffic: dict
    traffic_path: Path
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file by path (its name may hold '.' or '-')."""
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics a cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (cell in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


def resolve(bench: dict, cell: str, root: Path = ROOT) -> Cell:
    w = _by_name(bench["workloads"], cell, "workload")
    cfg_entry = _by_name(bench["configs"], w["config"], "config")
    cfg_path = root / cfg_entry["file"]
    traffic_path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
    for p in (cfg_path, traffic_path):
        if not p.is_file():
            raise SpecError(f"cell {cell!r}: missing {p}")
    config = json.loads(cfg_path.read_text())
    config.setdefault("name", w["config"])
    e2e, per_layer = cell_metrics(bench, cell)
    return Cell(
        name=cell, chips=int(w["chips"]), config=config,
        config_path=cfg_path, traffic=json.loads(traffic_path.read_text()),
        traffic_path=traffic_path, end_to_end=e2e, per_layer=per_layer,
    )


def maker(config: dict, root: Path = ROOT) -> ModuleType:
    return load_module(root / "chipbench" / "makers" / f"{config['maker']}.py",
                       config["maker"])


def driver(traffic: dict, root: Path = ROOT) -> ModuleType:
    return load_module(root / "chipbench" / "drivers" / f"{traffic['driver']}.py",
                       traffic["driver"])


def metric_reader(name: str, root: Path = ROOT) -> ModuleType:
    """The reader of one per-layer metric: its own file, else its family's
    (``mfu.offline`` and ``mfu.offline_floats`` share ``mfu.py``)."""
    metrics = root / "chipbench" / "metrics"
    own = metrics / f"{name}.py"
    path = own if own.is_file() else metrics / f"{name.split('.')[0]}.py"
    return load_module(path, path.stem.replace(".", "_"))
