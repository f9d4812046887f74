"""Find a cell, a configuration, a traffic mix and a per-layer metric by
the name ``BENCHMARK.json`` gives it. Everything is relative to this
file, so a copy of ``benchmarks/`` beside another ``BENCHMARK.json``
is a benchmark of its own (the temp-directory test relies on that)."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    path = os.path.join(BENCH_DIR, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    return read_json("workloads", name + ".json")


def config(name: str):
    """(sizes file, adapter module) of one configuration."""
    return (
        read_json("configs", name + ".json"),
        load_module("configs", name + ".py"),
    )


def traffic(name: str):
    """(parameters, the generator of the mix's ``kind``)."""
    mix = read_json("traffic", name + ".json")
    return mix, load_module("traffic", "kinds", mix["kind"] + ".py")


def layer_metric(name: str):
    """The reader of one per-layer metric; readers may import the
    helpers beside them (``from _shared import ...``)."""
    here = os.path.join(BENCH_DIR, "layer_metrics")
    if here not in sys.path:
        sys.path.insert(0, here)
    return load_module("layer_metrics", name + ".py")


def metrics_of(man: dict, section: str, cell_name: str) -> list[dict]:
    """The manifest's metrics of ``section`` that this cell reports: a
    metric with no ``workloads`` key is reported by every cell."""
    return [
        m
        for m in man[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
