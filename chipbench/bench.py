"""Where the benchmark finds its parts: every piece is looked up by name.

A cell (one entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Each of those, the cell's own
parameters, every metric and every architecture's reference and
operation counts live in files of their own under ``chipbench/``:

- ``configs/<config>.json``: the model as it is run, its source, the
  keys cut from the source, the engine's rows/len/blocks, the limit of
  the correctness check;
- ``traffic/<mix>.json``: the parameters of a mix, read by the
  generator module it names under ``gen/``;
- ``cells/<workload>.json``: the cell's fixed offered rate;
- ``metrics/<metric>.py``: a reader ``read(run)`` of one metric;
- ``reference/<kind>.py`` and ``counts/<kind>.py``: the plain float32
  forward and the operation/byte counts of one architecture.

So a later change adds a model, a mix, a cell or a metric with new files
alone.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload_spec(name: str, bench: dict = None) -> Dict:
    """Everything one cell needs, resolved from ``BENCHMARK.json``."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    cell = load_json(HERE / "cells" / f"{name}.json")

    def serves(m):
        return name in m.get("workloads", [name])

    return {
        "name": name,
        "chips": w["chips"],
        "config": config,
        "traffic": traffic,
        "cell": cell,
        "end_to_end": [m for m in bench["end_to_end"] if serves(m)],
        "per_layer": [m for m in bench["per_layer"] if serves(m)],
    }


def metric_reader(name: str) -> ModuleType:
    return load_module(HERE / "metrics" / f"{name}.py")


def generator(traffic: dict) -> ModuleType:
    return importlib.import_module(f"chipbench.gen.{traffic['generator']}")


def reference(config: dict) -> ModuleType:
    return importlib.import_module(f"chipbench.reference.{config['kind']}")


def counts(config: dict) -> ModuleType:
    return importlib.import_module(f"chipbench.counts.{config['kind']}")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
