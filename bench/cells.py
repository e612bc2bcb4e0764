"""Finds a cell's pieces by name: the cell in ``BENCHMARK.json``, its
configuration under ``configs/``, its traffic mix under ``traffic/``, the
module of its configuration's layer kind under ``kinds/`` and each
per-layer metric's reader under ``metrics/``.

A new configuration, mix, layer kind or metric is a new file plus a new
entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(CHECKOUT, "BENCHMARK.json")
KINDS_DIR = os.path.join(BENCH_DIR, "kinds")


def _load_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {what} at {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: str = BENCHMARK_JSON) -> dict:
    return _load_json(path, "benchmark definition")


def find_cell(name: str, bench: dict | None = None) -> dict:
    bench = bench if bench is not None else load_benchmark()
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    names = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: {names})")


def load_config(name: str) -> dict:
    cfg = _load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"),
                     f"configuration {name!r}")
    cfg["name"] = name
    return cfg


def load_traffic(name: str) -> dict:
    mix = _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"),
                     f"traffic mix {name!r}")
    mix["name"] = name
    return mix


def sized_config(cfg: dict, mix: dict, chips: int) -> dict:
    """The configuration as the cell runs it.  A mix with
    ``"vertices_per_chip": true`` gives every chip the configuration's
    ``num_vertices``, so the graph has ``chips`` times as many."""
    if mix.get("vertices_per_chip"):
        cfg = dict(cfg, num_vertices=cfg["num_vertices"] * chips)
    return cfg


def _load_module(path: str, module_name: str, what: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    return _load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                        f"bench_metric_{name}",
                        f"reader for metric {name!r}").read


def layer_kind(name: str):
    """The module ``kinds/<name>.py`` of a GNN layer kind: its weights,
    its layer equation for the reference and its work counts."""
    return _load_module(os.path.join(KINDS_DIR, f"{name}.py"),
                        f"bench_kind_{name}", f"GNN layer kind {name!r}")


def cell_metrics(cell: dict, bench: dict, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def hot_bytes(cfg: dict, mix: dict) -> int:
    """The hot-store budget the mix asks for, in bytes.

    ``{"share_of_layer0_feature_bytes": s}``: ``s`` times the bytes of the
    input features.  ``{"widest_hot_state": true}``: room for every
    vertex's partial state in the widest layer, so nothing is evicted.
    """
    rule = mix["hot_budget"]
    v, dims = cfg["num_vertices"], cfg["dims"]
    if "share_of_layer0_feature_bytes" in rule:
        return int(rule["share_of_layer0_feature_bytes"] * v * dims[0] * 4)
    if rule.get("widest_hot_state"):
        kind = layer_kind(cfg["kind"])
        widest = max(kind.hot_width(d) for d in dims[:-1])
        return v * widest * 4
    raise ValueError(f"traffic mix {mix['name']!r}: unknown hot_budget {rule}")
