"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Under the benchmark's directory (the first of ``paths``):

* ``configs/<config>.json`` (named by the configuration's ``file``);
* ``traffic/<traffic>.json`` -- the mix;
* ``cells/<workload>.json`` -- what one cell fixes: its offered rate,
  and optional overrides of the mix (``traffic``) or the engine
  settings (``engine``);
* ``models/<model_type>.py`` -- the code of one architecture, found
  by the configuration file's published ``model_type`` (``model``);
* ``metrics/<metric>.py`` -- one reader per metric, ``read(run)``.

A later cell, mix, configuration, architecture or metric is a new file;
no code here names any of them.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List


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
    model: ModuleType


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
    model_type = config.get("model_type")
    if model_type is None:
        raise ValueError(
            f"{conf_entry['file']} has no model_type: the benchmark finds "
            f"an architecture's code as "
            f"{os.path.join(data_dir, 'models', '<model_type>.py')}")
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
                per_layer, data_dir, model(data_dir, model_type))


def smoke(conf: Dict[str, Any]) -> Dict[str, Any]:
    """A configuration or mix with its ``smoke`` overrides applied
    (nested ``engine`` keys merge): the size of a rehearsal on the
    CPU."""
    out = copy.deepcopy(conf)
    over = conf.get("smoke", {})
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def _load(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # a dataclass looks its module up while the module runs
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def model(data_dir: str, model_type: str) -> ModuleType:
    """``models/<model_type>.py``: the architecture's ``Shape``,
    ``model_config``, ``make_weights``, ``sequence_gaps`` and
    ``decode_token_flops`` (bench/README.md)."""
    path = os.path.join(data_dir, "models", f"{model_type}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no code for model_type {model_type!r}: looked for {path}")
    return _load(path, f"bench_model_{model_type.replace('.', '_')}")


def reader(data_dir: str, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(data_dir, "metrics", f"{metric}.py")
    return _load(path, f"bench_metric_{metric.replace('.', '_')}").read
