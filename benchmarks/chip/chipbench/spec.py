"""Finds everything a cell needs by name, in files.

* the cell: an entry of ``workloads`` in ``BENCHMARK.json``, or, for a cell
  not (yet) in the benchmark, the ``cell`` entry of ``cells/<name>.json``;
* its configuration: ``configs/<config>.json`` (the file ``BENCHMARK.json``
  names for it);
* its architecture: ``archs/<architecture>.py``, where ``architecture`` is
  the configuration's key of that name (``default`` where it has none);
* its traffic: ``traffic/<traffic>.json``;
* its limits for ``correct``: ``cells/<name>.json``;
* its metrics: every ``end_to_end`` and ``per_layer`` entry of
  ``BENCHMARK.json`` that lists the cell (or lists no cells), each read by
  ``metrics/<metric name>.py``;
* the chip's peaks: ``peaks.json``, keyed by ``device_kind``.

A later change adds an architecture, a configuration, a traffic mix, a
cell or a metric by adding files and entries; none of this code changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

from . import traffic as traffic_mod

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
DEFAULT_ARCH = "default"


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(name: str | None = None,
                 bench_dir: str = BENCH_DIR) -> ModuleType:
    """``archs/<name>.py`` (``default.py`` for None): everything that
    depends on the layer equations.  It supplies

    * ``from_config(cfg)``: a frozen, hashable model description with at
      least ``name``, ``vocab_size``, ``padded_vocab`` and ``num_layers``;
    * ``make_params(model, seed, device)``: the seeded weights in the
      program's layout, made by one jitted call;
    * ``logits_and_margins(model, params, tokens, start, n, quant)``: the
      fp32 reference's logits and a router margin per position (``+inf``
      where nothing is routed), the fp8 control with ``quant="fp8"``;
    * ``program_sizes(model)``: ``{ModelConfig attribute: value}`` that the
      program has to match;
    * the counts ``linear_per_token``, ``head``, ``prefill_attention``,
      ``prefill``, ``decode_attention`` and ``decode_token``, as
      ``chipbench/flops.py`` defines them for the default one.
    """
    name = name or DEFAULT_ARCH
    path = os.path.join(bench_dir, "archs", f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no architecture {name!r}: {path} does not exist")
    return _load(path, f"chipbench_arch_{name}")


@dataclass
class Cell:
    name: str
    entry: dict                 # name, config, traffic, chips, why
    config: dict                # the configuration file's contents
    model: object               # arch.from_config(config)
    traffic: dict
    limits: dict
    end_to_end: list            # BENCHMARK.json metric entries
    per_layer: list
    arch: ModuleType | None = None   # None: load the config's own

    def __post_init__(self):
        if self.arch is None:
            self.arch = architecture(self.config.get("architecture"))


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of the checkout at ``root``, with every file found
    in that checkout's copy of the benchmark."""
    bench = benchmark(root)
    bench_dir = os.path.join(root, os.path.relpath(BENCH_DIR, ROOT))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    cell_file = os.path.join(bench_dir, "cells", f"{name}.json")
    extra = _json(cell_file) if os.path.exists(cell_file) else {}
    if entry is None:
        entry = extra.get("cell")
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json or cells/")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    conf_path = (os.path.join(root, conf["file"]) if conf is not None else
                 os.path.join(bench_dir, "configs", f"{entry['config']}.json"))
    config = _json(conf_path)
    arch = architecture(config.get("architecture"), bench_dir)
    traffic = traffic_mod.load(
        os.path.join(bench_dir, "traffic", f"{entry['traffic']}.json"))
    return Cell(name, entry, config, arch.from_config(config), traffic,
                extra.get("limits", {}),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], arch)


def reader(metric: str):
    """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    return _load(path, f"chipbench_metric_{metric.replace('.', '_')}").read


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table["devices"][device_kind]
