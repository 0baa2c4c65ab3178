"""Finds everything by name: a cell in BENCHMARK.json, its configuration (the
file BENCHMARK.json names, under ``configs/``), its traffic
``traffic/<traffic>.json``, its fixed rate ``cells/<cell>.json``, its
collection generator ``generators/<config's generator>.py`` and each per-layer
metric's reader ``metrics/<metric>.py``.  Adding any of them adds files and
never edits one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    rate_qps: float | None  # None until a knee sweep has written cells/<cell>.json
    end_to_end: list[dict]  # this cell's end-to-end metric entries
    per_layer: list[dict]  # this cell's per-layer metric entries


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load_json(root / conf["file"]),
        traffic=_load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        rate_qps=_rate(name),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def _rate(cell_name: str) -> float | None:
    path = BENCH / "cells" / f"{cell_name}.json"
    return float(_load_json(path)["rate_qps"]) if path.exists() else None


def _module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
    return _module("metrics", metric).read


def collection(config: dict, seed: int):
    """The configuration's collection from ``seed``, made by the generator its
    ``generator`` key names."""
    return _module("generators", config["generator"]).synthesize(config["collection"], seed)
