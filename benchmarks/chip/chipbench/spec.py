"""Cells of ``BENCHMARK.json`` and the files that belong to each, by name.

A cell ``<config>.<traffic>`` reads ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json``; a per-layer metric
``<metric>`` is the reader ``metrics/<metric>.py``.  Adding a cell, a
configuration, a traffic mix or a metric adds files and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]   # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                                # the checkout


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    limits: dict[str, float]
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None,
              root: pathlib.Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {', '.join(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(root / configs[w["config"]]["file"]),
        traffic=_read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def metric_reader(name: str) -> Callable[[Any], float | None]:
    """``read(run)`` of ``metrics/<name>.py``; ``None`` when it finds nothing."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
