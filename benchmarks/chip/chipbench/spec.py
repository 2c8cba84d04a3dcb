"""Finds everything a cell needs by name, in files.

* the cell: an entry of ``workloads`` in ``BENCHMARK.json``, or, for a cell
  not (yet) in the benchmark, the ``cell`` entry of ``cells/<name>.json``;
* its configuration: ``configs/<config>.json`` (the file ``BENCHMARK.json``
  names for it);
* its traffic: ``traffic/<traffic>.json``;
* its limits for ``correct``: ``cells/<name>.json``;
* its metrics: every ``end_to_end`` and ``per_layer`` entry of
  ``BENCHMARK.json`` that lists the cell (or lists no cells), each read by
  ``metrics/<metric name>.py``;
* the chip's peaks: ``peaks.json``, keyed by ``device_kind``.

A later change adds a configuration, a traffic mix, a cell or a metric by
adding files and entries; none of this code changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

from . import model as model_mod
from . import traffic as traffic_mod

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    entry: dict                 # name, config, traffic, chips, why
    config: dict                # the configuration file's contents
    model: model_mod.Model
    traffic: dict
    limits: dict
    end_to_end: list            # BENCHMARK.json metric entries
    per_layer: list


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    cell_file = os.path.join(BENCH_DIR, "cells", f"{name}.json")
    extra = _json(cell_file) if os.path.exists(cell_file) else {}
    if entry is None:
        entry = extra.get("cell")
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json or cells/")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    conf_path = (os.path.join(root, conf["file"]) if conf is not None else
                 os.path.join(BENCH_DIR, "configs", f"{entry['config']}.json"))
    config, model = model_mod.load(conf_path)
    traffic = traffic_mod.load(
        os.path.join(BENCH_DIR, "traffic", f"{entry['traffic']}.json"))
    return Cell(name, entry, config, model, traffic, extra.get("limits", {}),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str):
    """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table["devices"][device_kind]
