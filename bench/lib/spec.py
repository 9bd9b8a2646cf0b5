"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell ``<config>.<traffic>`` reads ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json``; each per-layer
metric ``<name>`` that lists the cell is read by ``metrics/<name>.py``.
Adding a cell or a metric is adding files and entries: nothing here names
one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_spec(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, cell: str, bench: pathlib.Path = BENCH) -> Cell:
    """The cell named ``cell`` with its files loaded; KeyError if the
    benchmark has no such cell."""
    w = {x["name"]: x for x in spec["workloads"]}[cell]
    conf = {x["name"]: x for x in spec["configs"]}[w["config"]]
    return Cell(
        name=cell, chips=int(w["chips"]),
        config=_read(bench.parent / conf["file"]),
        traffic=_read(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_read(bench / "limits" / f"{cell}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, cell)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, cell)])


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
