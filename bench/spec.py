"""Resolve a workload name from ``BENCHMARK.json`` into the files that
describe it.  Nothing here knows any particular cell: a new cell, traffic
mix, configuration, block or metric is found by its name."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
    pass


@dataclass
class Metric:
    name: str
    unit: str
    reader: Optional[Callable] = None     # per-layer metrics only


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                 # bench/configs/<config>.json
    block: object                # bench/blocks/<config's "block">.py
    traffic_name: str
    traffic: dict                # bench/traffic/<traffic>.json
    cell: dict                   # bench/cells/<workload>.json
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _load_module(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(record)``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"per-layer metric {name!r} has no reader at {path}")
    return _load_module(path, "bench_metric", name).read


def load_block(root: str, name: str):
    """``bench/blocks/<name>.py``: what the benchmark knows of one kind of
    layer (its program configuration, weights, reference and work)."""
    path = os.path.join(root, "bench", "blocks", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"block {name!r} has no module at {path}")
    return _load_module(path, "bench_block", name)


def _applies(metric: dict, workload: str, reported: Optional[set]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    if reported is None:
        return True
    return metric["moves"] in reported


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, None)]
    reported = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if _applies(m, workload, reported)]

    config = _load_json(os.path.join(root, conf["file"]))
    if "block" not in config:
        raise SpecError(f"configuration {conf['file']} names no block")

    def metric(m: dict, per_layer: bool) -> Metric:
        return Metric(name=m["name"], unit=m["unit"],
                      reader=load_reader(root, m["name"]) if per_layer
                      else None)

    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, block=load_block(root, config["block"]),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        f"{w['traffic']}.json")),
        cell=_load_json(os.path.join(root, "bench", "cells",
                                     f"{workload}.json")),
        end_to_end=[metric(m, False) for m in e2e],
        per_layer=[metric(m, True) for m in per])
