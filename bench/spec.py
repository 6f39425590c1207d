"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Under the benchmark's directory (the first of ``paths``):

* ``configs/<config>.json`` (named by the configuration's ``file``);
* ``traffic/<traffic>.json`` -- the mix;
* ``cells/<workload>.json`` -- what one cell fixes: its offered rate,
  and optional overrides of the mix (``traffic``) or the engine
  settings (``engine``);
* ``metrics/<metric>.py`` -- one reader per metric, ``read(run)``.

A later cell, mix, configuration or metric is a new file; no code here
names any of them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    cell: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    data_dir: str


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, workload: str, reported: List[str]) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def find(bench_path: str, workload: str) -> Cell:
    root = os.path.dirname(os.path.abspath(bench_path))
    bench = _load_json(bench_path)
    data_dir = os.path.join(root, bench["paths"][0])
    try:
        w = next(x for x in bench["workloads"] if x["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r} in {bench_path}") from None
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _load_json(os.path.join(root, conf_entry["file"]))
    config["name"] = conf_entry["name"]
    mix = _load_json(os.path.join(data_dir, "traffic",
                                  f"{w['traffic']}.json"))
    cell = _load_json(os.path.join(data_dir, "cells", f"{workload}.json"))
    mix = {**mix, **cell.get("traffic", {})}
    if cell.get("engine"):
        config["engine"] = {**config["engine"], **cell["engine"]}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, names)]
    return Cell(workload, int(w["chips"]), config, mix, cell, e2e,
                per_layer, data_dir)


def reader(data_dir: str, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(data_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
