"""Finds everything a cell needs by the names ``BENCHMARK.json`` gives.

A configuration is the JSON file its entry names, a traffic mix is
``bench/traffic/<traffic>.json`` and a metric is ``bench/metrics/<name>.py``
(a module with ``read(run)``). A later change adds a configuration, a mix,
a metric or a cell by adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
BENCHMARK_FILE = ROOT / "BENCHMARK.json"
TRAFFIC_DIR = BENCH_DIR / "traffic"
METRICS_DIR = BENCH_DIR / "metrics"


class UnknownName(LookupError):
    """A cell, configuration, mix or metric that no file defines."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the mix's parameters
    end_to_end: tuple     # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def load_benchmark(path: Path = BENCHMARK_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise UnknownName(f"no file {path}")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(bench: dict, name: str) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics loaded."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise UnknownName(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise UnknownName(f"workload {name!r} names no configuration "
                          f"{w['config']!r}")
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(TRAFFIC_DIR / f"{w['traffic']}.json")
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key goes wherever the
    # end-to-end metric it moves is reported
    per_layer = tuple(
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in reported)
    )
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, metrics_dir: Path = METRICS_DIR):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        raise UnknownName(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries, run, metrics_dir: Path = METRICS_DIR) -> dict:
    """``{name: {"value", "unit"}}`` for every entry whose reader found
    something; a reader that returns None leaves its metric out."""
    out = {}
    for m in entries:
        value = metric_reader(m["name"], metrics_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
