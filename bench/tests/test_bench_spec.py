"""Every name in BENCHMARK.json resolves to its files, and the file keeps
the benchmark contract's shape."""
import dataclasses
import importlib
import json
import os
import re
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [BENCH, os.path.join(BENCH, "..", "src")]

from benchlib import spec  # noqa: E402

B = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in B["end_to_end"]}


@pytest.mark.parametrize("wl", B["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(wl):
    doc = spec.config_doc(B, wl["config"])
    mod, attr = doc["program"].split(":")
    cfg = dataclasses.asdict(getattr(importlib.import_module(mod), attr))
    for key, want in doc["model"].items():
        assert cfg[key] == want, key
    assert os.path.isfile(spec.reference_path(doc["reference"]))
    traffic = spec.traffic_doc(wl["traffic"])
    import run as bench_run  # bench/run.py

    assert callable(bench_run.runner_for(traffic["runner"]))
    limits = spec.limits_doc(wl["name"])
    assert limits and all(v >= 0 for v in limits.values())
    reported = spec.end_to_end_for(B, wl["name"])
    assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
    layers = spec.per_layer_for(B, wl["name"])
    assert layers
    for m in layers:
        assert m["moves"] in {e["name"] for e in reported}
        assert callable(spec.load_module(spec.metric_path(m["name"])).read)


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves(m):
    assert callable(spec.load_module(spec.metric_path(m["name"])).read)
    assert m["moves"] in E2E
    for w in m["workloads"]:
        spec.workload(B, w)
        assert w in E2E[m["moves"]].get("workloads", [w])


def test_contract_shape():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in B[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    four = [w["name"] for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 2)
    for c in B["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in B["workloads"])
    assert len(json.dumps(B)) < 64 * 1024
