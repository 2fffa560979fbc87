"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

    configs/<config>.json      the model configuration, with its layer table
    traffic/<traffic>.json     the traffic mix, read by ``imagegen``
    limits/<workload>.json     the correctness limits of the cell
    metrics/<metric>.py        one per-layer metric's reader
    reference/<name>.py        the plain reference a config names
    peaks.json                 the chips' peaks, by ``device_kind``

Adding a cell, a mix, a configuration or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def _json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def global_batch(self) -> int:
        return self.config["batch_per_chip"] * self.chips

    def reference(self) -> ModuleType:
        name = self.config["reference"]
        return load_module(os.path.join(BENCH_DIR, "reference", name + ".py"),
                           "chipbench_reference_" + name)

    def readers(self) -> Dict[str, ModuleType]:
        return {m["name"]: load_module(
            os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
            "chipbench_metric_" + m["name"].replace(".", "_"))
            for m in self.per_layer}


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_json("traffic", w["traffic"] + ".json"),
        limits=_json("limits", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def peaks(device_kind: str) -> dict:
    table = _json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
