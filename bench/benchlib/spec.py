"""BENCHMARK.json and the files each of its names resolves to.

Every configuration, traffic mix, per-layer metric, plain reference and
set of limits is a file of its own, found by name:

* ``bench/configs/<config>.json``   (named by the config entry's ``file``)
* ``bench/traffic/<traffic>.json``
* ``bench/metrics/<metric>.py``     (a reader with ``read(run)``)
* ``bench/reference/<reference>.py`` (named by the config file)
* ``bench/limits/<workload>.json``  (the limits ``correct`` is judged by)

A new cell or metric is new files and new entries in BENCHMARK.json.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any

#: The root of the checkout: ``bench/benchlib/`` is two levels below it.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "bench")


class SpecError(SystemExit):
    """BENCHMARK.json names something that is not there."""


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config_entry(bench: dict, name: str) -> dict:
    return _by_name(bench["configs"], name, "configuration")


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def config_doc(bench: dict, config: str) -> dict:
    return _json(os.path.join(ROOT, config_entry(bench, config)["file"]))


def traffic_path(traffic: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{traffic}.json")


def traffic_doc(traffic: str) -> dict:
    return _json(traffic_path(traffic))


def limits_path(workload_name: str) -> str:
    return os.path.join(BENCH_DIR, "limits", f"{workload_name}.json")


def limits_doc(workload_name: str) -> dict:
    return _json(limits_path(workload_name))


def metric_path(metric: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{metric}.py")


def reference_path(reference: str) -> str:
    return os.path.join(BENCH_DIR, "reference", f"{reference}.py")


def load_module(path: str) -> Any:
    """Import a file by path; its module name is made from the file name."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, workload_name: str) -> bool:
    return "workloads" not in entry or workload_name in entry["workloads"]


def end_to_end_for(bench: dict, workload_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"] if _reports(m, workload_name)]


def per_layer_for(bench: dict, workload_name: str) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those with no list whose end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, workload_name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if workload_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out
