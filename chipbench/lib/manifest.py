"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by the name the manifest gives it:

    configs/<config>.json     model sizes, serving flags, source, cuts
    traffic/<traffic>.json    parameters of the one traffic generator
                              (lib/traffic.py), with any table it names
    metrics/<metric>.py       the reader of one per-layer metric
    limits/<cell>.json        the limits of the comparison that decides
                              ``correct``, with the readings they came from

A new cell, configuration, mix or metric is therefore new files plus new
manifest entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import traffic as traffic_mod

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    limits: dict            # limits/<cell>.json
    chips: int
    end_to_end: list        # manifest entries of the metrics this cell reports
    per_layer: list
    bench_dir: str = BENCH_DIR


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def reports(metric: dict, cell: str, moved: set) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, or, without the key, every cell (end-to-end) or every cell
    that reports the end-to-end metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in moved


def resolve(name: str, manifest: dict | None = None,
            root: str = ROOT) -> Cell:
    """The cell ``name`` of the manifest with its files loaded; ``root``
    is the directory that holds ``BENCHMARK.json`` and ``chipbench/``."""
    manifest = manifest if manifest is not None else load_manifest(root)
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = traffic_mod.load(os.path.join(bench_dir, "traffic",
                                            w["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", name + ".json"))
    e2e = [m for m in manifest["end_to_end"] if reports(m, name, set())]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"] if reports(m, name, moved)]
    return Cell(name, config, traffic, limits, int(w["chips"]), e2e,
                per_layer, bench_dir)


def load_metric(name: str, bench_dir: str = BENCH_DIR):
    """The reader module ``metrics/<name>.py`` (names may hold dots)."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
