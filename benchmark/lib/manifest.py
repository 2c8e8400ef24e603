"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by name:

    benchmark/configs/<config>.json          benchmark/traffic/<traffic>.json
    benchmark/builders/<builder>.py          benchmark/reference/<builder>.py
    benchmark/drivers/<driver>.py            benchmark/layer_metrics/<metric>.py

There is no table of them in code: a later PR adds a cell with entries in
``BENCHMARK.json`` plus files, and edits none that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, loaded by path so that a
    name needs to be no Python identifier."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dictionaries merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list     # the manifest's entries this cell reports
    per_layer: list
    root: str = ROOT

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)

    @property
    def builder(self):
        return self.module("builders", self.config["builder"])

    @property
    def reference(self):
        return self.module("reference", self.config["builder"])

    @property
    def driver(self):
        return self.module("drivers", self.traffic["driver"])

    def reader(self, metric: str):
        return self.module("layer_metrics", metric)


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def listed(manifest: dict, cell: str) -> tuple:
    """``(end_to_end, per_layer)``: the entries ``cell`` reports, read off the manifest alone. A
    per-layer entry is reported where it applies and the metric it moves is reported."""
    e2e = [m for m in manifest["end_to_end"] if applies(m, cell)]
    reported = {m["name"] for m in e2e}
    return e2e, [m for m in manifest["per_layer"] if applies(m, cell) and m["moves"] in reported]


def resolve(manifest: dict, cell: str, *, root: str = ROOT, rehearse: bool = False) -> Cell:
    """The cell ``cell`` with its configuration, traffic and metric lists. With
    ``rehearse`` the tiny sizes of each file's ``rehearsal`` block are laid
    over it (CPU tests only)."""
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if cell not in by_name:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json; there are {sorted(by_name)}")
    w = by_name[cell]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json(root, "traffic", w["traffic"])
    if rehearse:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    e2e, per_layer = listed(manifest, cell)
    return Cell(cell, int(w["chips"]), w["config"], config, w["traffic"], traffic, e2e,
                per_layer, root)
