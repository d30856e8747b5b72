"""Resolve a cell by name to its data files. No jax here.

A cell ``<config>.<mix>`` is one ``workloads`` entry of ``BENCHMARK.json``: it
names a configuration (the ``file`` its ``configs`` entry gives, by convention
``benchmarks/configs/<config>.json``), a traffic mix (``benchmarks/traffic/<mix>.json``)
and optionally facts of the pair (``benchmarks/cells/<cell>.json``: the loss
band). Nothing in the harness compares a name with a constant: a new cell is
new files and a new entry. Only names that ``BENCHMARK.json`` lists resolve.
"""

from __future__ import annotations

import copy
import json
import os
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/
ROOT = os.path.dirname(HERE)


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def merged(base: dict, *overrides: dict) -> dict:
    """Deep merge: dicts merge key by key, anything else is replaced."""
    out = copy.deepcopy(base)
    for over in overrides:
        for k, v in (over or {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = merged(out[k], v)
            else:
                out[k] = copy.deepcopy(v)
    return out


def benchmark_json() -> dict:
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    facts: dict  # benchmarks/cells/<cell>.json, {} when absent
    end_to_end: list = field(default_factory=list)  # metric entries of this cell
    per_layer: list = field(default_factory=list)

    def train_config(self, rehearse: str | None) -> dict:
        return merged(self.config.get("train_config", {}),
                      self.traffic.get("train_config", {}),
                      *self._rehearse("train_config", rehearse))

    def data_spec(self, rehearse: str | None) -> dict:
        return merged(self.config.get("data", {}), self.traffic.get("data", {}),
                      *self._rehearse("data", rehearse))

    def num_sites(self, rehearse: str | None) -> int:
        for part in (self.traffic, self.config):
            over = part.get("rehearse", {}).get(rehearse or "", {})
            if "num_sites" in over:
                return int(over["num_sites"])
        return int(self.config["num_sites"])

    def _rehearse(self, key: str, rehearse: str | None):
        if not rehearse:
            return ()
        if rehearse not in self.config.get("rehearse", {}):
            raise KeyError(
                f"{self.config_name} has no rehearsal size {rehearse!r}")
        return tuple(part.get("rehearse", {}).get(rehearse, {}).get(key, {})
                     for part in (self.config, self.traffic))


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = benchmark_json()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json lists no workload {name!r}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config_path = os.path.join(ROOT, cfg_entry["file"])
    traffic_path = os.path.join(HERE, "traffic", entry["traffic"] + ".json")
    for path in (config_path, traffic_path):
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"cell {name!r}: {os.path.relpath(path, ROOT)} does not exist")
    facts_path = os.path.join(HERE, "cells", name + ".json")
    return Cell(
        name=name, config_name=entry["config"], traffic_name=entry["traffic"],
        chips=int(entry["chips"]),
        config=read_json(config_path), traffic=read_json(traffic_path),
        facts=read_json(facts_path) if os.path.isfile(facts_path) else {},
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def layer_metric(name: str) -> dict:
    return read_json(os.path.join(HERE, "layer_metrics", name + ".json"))


def peaks() -> dict:
    """``peaks.json``: published peaks of one chip by ``device_kind``."""
    return {k: v for k, v in read_json(os.path.join(HERE, "peaks.json")).items()
            if not k.startswith("_")}
