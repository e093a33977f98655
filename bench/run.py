#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell names a configuration
(``bench/configs/``), a traffic mix (``bench/traffic/``, whose ``runner``,
``module:function`` under ``bench/``, drives the program) and its limits
(``bench/limits/``). Set-up makes the inputs and weights from ``--seed``
and warms every shape; the window then runs for ``--seconds``. After it
the served outputs are compared with a plain float32 reference, and the
last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from a profiler trace and the host's records), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared and
its limit, also printed as the last lines of stderr.

Without a TPU, with fewer chips than the cell asks for, or on a chip that
is not in the peaks table, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "reference"), os.path.join(ROOT, "src")]
# libtpu's logs stay inside the checkout, not in a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "runs", "tpu_logs"))

from benchlib import spec  # noqa: E402

def program_config(doc: dict):
    """The program's config object the file names, checked against every
    size the file states."""
    mod_name, attr = doc["program"].split(":")
    cfg = getattr(importlib.import_module(mod_name), attr)
    actual = dataclasses.asdict(cfg)
    for key, want in doc["model"].items():
        if actual.get(key, "<absent>") != want:
            raise spec.SpecError(
                f"{doc['program']}.{key} is {actual.get(key, '<absent>')!r}; "
                f"the configuration file states {want!r}")
    return cfg


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench/run.py needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"the cell asks for {n} chips; JAX found {len(devs)}")
    return devs[:n]


def runner_for(name: str):
    """The function ``module:function`` that a traffic file's ``runner``
    names; ``module`` is imported from ``bench/``."""
    mod, _, fn = name.partition(":")
    try:
        return getattr(importlib.import_module(mod), fn)
    except (ImportError, AttributeError, ValueError) as e:
        raise spec.SpecError(f"traffic runner {name!r} does not resolve: {e}") from None


@dataclasses.dataclass
class Prepared:
    cell: object  # benchlib.cell.Cell
    workload: dict
    doc: dict
    e2e_entries: list
    layer_entries: list
    readers: dict
    peaks: dict


def prepare(workload: str, seed: int, seconds: float, trace: bool) -> Prepared:
    """Resolve a cell's files, set up JAX, check the chips: everything
    before its runner runs."""
    bench = spec.load_benchmark()
    wl = spec.workload(bench, workload)
    doc = spec.config_doc(bench, wl["config"])
    traffic = spec.traffic_doc(wl["traffic"])
    limits = spec.limits_doc(wl["name"])
    e2e_entries = spec.end_to_end_for(bench, wl["name"])
    layer_entries = spec.per_layer_for(bench, wl["name"])
    readers = {m["name"]: spec.load_module(spec.metric_path(m["name"]))
               for m in layer_entries}
    reference = spec.load_module(spec.reference_path(doc["reference"]))
    try:
        from repro.launch.runtime import RUNS_DIR, setup_compile_cache
    except ImportError as e:
        raise SystemExit(f"the program under test is not in this checkout: {e}")

    from benchlib.cell import Cell
    from benchlib.peaks import peaks_for

    setup_compile_cache()
    devices = require_chips(wl["chips"])
    peaks = peaks_for(devices[0].device_kind)
    cfg = program_config(doc)
    work_dir = os.path.join(RUNS_DIR, "bench", wl["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    cell = Cell(workload=wl["name"], cfg=cfg, sizes=doc["model"], reference=reference,
                traffic=traffic, limits=limits, seed=seed, seconds=seconds,
                trace=trace, devices=devices, work_dir=work_dir)
    return Prepared(cell, wl, doc, e2e_entries, layer_entries, readers, peaks)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    p = prepare(args.workload, args.seed, args.seconds, bool(args.trace))
    cell = p.cell

    import jax

    from benchlib.cell import WINDOW_SPAN, Run, is_correct, log

    try:
        out = runner_for(cell.traffic["runner"])(cell)
        values = dict(out.e2e, setup_s=out.window_start - T_PROCESS)
        metrics, breakdown, dev_extra = {}, None, {}
        if not args.trace:
            for m in p.e2e_entries:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            from benchlib.trace import reduce_file

            if out.trace_file is None:
                raise SystemExit("the traced run wrote no trace")
            t_red = time.monotonic()
            red = reduce_file(out.trace_file, WINDOW_SPAN)
            if not red.devices:
                raise SystemExit("the trace holds no TPU device")
            run = Run(workload=cell.workload, sizes=cell.sizes, traffic=cell.traffic,
                      peaks=p.peaks, chips=len(cell.devices), e2e=values, host=out.host,
                      trace=red)
            for m in p.layer_entries:
                v = p.readers[m["name"]].read(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            breakdown = red.breakdown()
            dev_extra = {"busy_s": red.mean_busy_s(), "window_s": red.window_s}
            log(f"trace reduced in {time.monotonic() - t_red:.1f} s")
    finally:
        shutil.rmtree(cell.work_dir, ignore_errors=True)

    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    correct = is_correct(out)
    d = cell.devices[0]
    result = {
        "correct": correct, "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics,
        "device": {"platform": d.platform, "kind": d.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": out.memory_peak_bytes,
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    log(f"notes: {json.dumps(out.notes)}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
