"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a JSON file of
its own (``configs/<config>.json`` as listed in ``BENCHMARK.json``,
``traffic/<traffic>.json``), and each per-layer metric is a reader
``metrics/<name>.py``.  Adding a cell, a configuration or a metric is
adding files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str, bench: Optional[dict] = None) -> dict:
    """The workload entry, with its configuration and traffic loaded."""
    bench = bench or benchmark()
    try:
        wl = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return {
        "workload": wl,
        "config": _load(os.path.join(ROOT, conf["file"])),
        "traffic": _load(os.path.join(BENCH_DIR, "traffic", wl["traffic"] + ".json")),
        "limits": _load(os.path.join(BENCH_DIR, "limits", wl["config"] + ".json")),
    }


def metric_readers(bench: Optional[dict] = None) -> Dict[str, Callable]:
    """``{name: (read, unit)}`` for every per-layer metric."""
    bench = bench or benchmark()
    out = {}
    for m in bench["per_layer"]:
        path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[m["name"]] = (mod.read, m["unit"])
    return out
