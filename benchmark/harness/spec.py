"""Finding a cell's files by the names in BENCHMARK.json. No JAX."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
# Scratch of a run: model YAMLs, stamp files, traces. Inside the checkout, at
# a fixed path, listed in .gitignore.
SCRATCH = os.path.join(ROOT, ".bench_scratch")


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def config(name: str) -> dict:
    """A configuration's file, under the name BENCHMARK.json gives it."""
    entry = next(c for c in manifest()["configs"] if c["name"] == name)
    return {"name": name, **_json(ROOT, entry["file"])}


def cell(workload: str) -> dict:
    """Everything one workload needs, each piece from its own file."""
    man = manifest()
    entry = next(w for w in man["workloads"] if w["name"] == workload)
    out = {
        "name": workload,
        "chips": int(entry["chips"]),
        "cell": _json(BENCH, "cells", f"{workload}.json"),
        "config": config(entry["config"]),
        "mix": _json(BENCH, "traffic", f"{entry['traffic']}.json"),
        "end_to_end": [m for m in man["end_to_end"] if _applies(m, workload)],
        "per_layer": [m for m in man["per_layer"] if _applies(m, workload)],
    }
    return out


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reader(metric_name: str):
    """The per-layer metric's own reader: benchmark/layer_metrics/<name>.json
    names a module under benchmark/reducers/ and its arguments."""
    spec = _json(BENCH, "layer_metrics", f"{metric_name}.json")
    mod = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
    return lambda ctx: mod.read(ctx, **spec.get("args", {}))


def peaks(device_kind: str) -> dict:
    table = _json(BENCH, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "benchmark/peaks.json: add it with its source")
    return table[device_kind]
