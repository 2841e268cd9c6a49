"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<traffic>.json``); a per-layer metric is read by
``bench/metrics/<name>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def check_names(spec: dict) -> None:
    """Raise on a name, unit or ``better`` outside the contract's limits."""
    names = []
    for c in spec["configs"]:
        names.append(c["name"])
        names.extend(c["reduced"])
    for w in spec["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            names.append(m["name"])
            if not UNIT.match(m["unit"]):
                raise ValueError(f"unit {m['unit']!r} of {m['name']}")
            if m["better"] not in ("lower", "higher"):
                raise ValueError(f"better {m['better']!r} of {m['name']}")
            if m["source"] not in SOURCES:
                raise ValueError(f"source {m['source']!r} of {m['name']}")
    for n in names:
        if not NAME.match(n):
            raise ValueError(f"name {n!r}")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in spec[group]]
        if len(seen) != len(set(seen)):
            raise ValueError(f"duplicate name in {group}")


def cell(name: str, spec: dict, root: str = ROOT):
    """(workload entry, configuration dict, traffic dict) of cell ``name``."""
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    ce = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = load_json(os.path.join(root, ce["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic", wl["traffic"] + ".json"))
    return wl, config, traffic.validate(mix)


def metrics_of(name: str, spec: dict, group: str):
    """The ``group`` metrics that cell ``name`` reports."""
    return [m for m in spec[group] if name in m.get("workloads", [name])]


def reader(metric: str, root: str = ROOT):
    """The ``read(record)`` function of per-layer metric ``metric``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
