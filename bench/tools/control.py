#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/`` are set from, on the chip.

For each seed, in one process: the cell's runner runs a window as a run
does and gives the program's reading of each number compared; then the
control, the plain reference computed in the next precision down (fp8
products), is read on the same requests: at each served position, the gap
under the float32 reference of the token that the fp8 reference puts
first, and the error of its logits at each request's first served
position. The control's readings go through the run's own comparison in
the program's place, and ``control_correct`` is what ``correct`` would
read.

``--set key=value`` overrides a key of the cell's traffic file (a JSON
value), for readings off the cell's own mix.

One JSON line per seed on stdout. The benchmark's runs never run this.
Run from the root of the checkout::

    python bench/tools/control.py --workload <name> --seeds 1,2,3 --seconds 10
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402  (bench/run.py)

def control_outcome(cell, out):
    """``out`` with the control's readings in place of the program's."""
    from benchlib.serve import compare
    from benchlib.weights import make_params, program_shapes

    params = make_params(program_shapes(cell.cfg), cell.seed)
    ctl = compare(cell, params, out.compared, control=True)
    checks = {k: ((ctl[k], lim) if k in ctl else (v, lim))
              for k, (v, lim) in out.checks.items()}
    return dataclasses.replace(out, checks=checks)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    from benchlib.cell import is_correct

    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        p = bench_run.prepare(args.workload, seed, args.seconds, trace=False)
        for kv in args.set:
            k, v = kv.split("=", 1)
            p.cell.traffic[k] = json.loads(v)
        out = bench_run.runner_for(p.cell.traffic["runner"])(p.cell)
        line = {"seed": seed, "correct": is_correct(out),
                **{k: v for k, (v, _) in out.checks.items()},
                "e2e": out.e2e, "notes": out.notes}
        if not args.no_control:
            ctl = control_outcome(p.cell, out)
            line["control"] = {k: v for k, (v, _) in ctl.checks.items()}
            line["control_correct"] = is_correct(ctl)
        line["seconds"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
