#!/usr/bin/env python3
"""Print the program's spans in one profiler trace, and what the device
did under them.

    python bench/tools/program_breakdown.py <trace.xplane.pb>

For each program span name (``repro.tracing``): how many spans, their
self time and inclusive time, and the device idle time whose gaps fall
inside them (innermost program span, else the innermost ``bench.`` span);
then each numeric arg and count the spans carried, summed: bytes and
blocks read, decompressed and copied to the device, and the traces,
cache loads and compiles of each model call.
Inside the ``bench.window`` span where the trace has one, else over the
whole trace. Runs on any host; it reads the file with JAX alone.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

from benchlib import program_spans  # noqa: E402


def table(spans: program_spans.ProgramSpans) -> list[str]:
    names = sorted(set(spans.count) | set(spans.idle_s),
                   key=lambda n: -spans.self_s.get(n, spans.idle_s.get(n, 0.0)))
    rows = [f"window {(spans.window[1] - spans.window[0]) * 1e-9:.6f} s; "
            f"roots: restore {spans.count.get('restore', 0)}, "
            f"serve.step_batch {spans.count.get('serve.step_batch', 0)}",
            f"{'span':<24}{'count':>7}{'self_s':>13}{'total_s':>13}{'idle_s':>13}"]
    for n in names:
        rows.append(f"{n:<24}{spans.count.get(n, 0):>7}{spans.self_s.get(n, 0.0):>13.6f}"
                    f"{spans.total_s.get(n, 0.0):>13.6f}{spans.idle_s.get(n, 0.0):>13.6f}")
    rows.append("args and counts, summed over the spans of each name:")
    for n in names:
        if spans.args.get(n):
            rows.append(f"  {n}: " + ", ".join(
                f"{k} {v:g}" for k, v in sorted(spans.args[n].items())))
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file")
    args = ap.parse_args(argv)
    spans = program_spans.load(args.trace)
    if spans is None:
        print(f"{args.trace} holds no program span", file=sys.stderr)
        return 1
    print("\n".join(table(spans)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
