"""The program's own spans in a run's trace, for the per-layer readers.

The program opens spans through ``repro.tracing`` (``restore``,
``restore.*``, ``blockstore.*``, ``serve.*``), each a
``jax.profiler.TraceAnnotation`` whose numbers are event stats. This
module reads them from the ``.xplane.pb`` the run's ``Tracer`` wrote
(``runs/bench/<workload>/trace/``), inside the ``bench.window`` span, and
gives per program span name:

* ``self_s``: the time of each span less the part of it that program
  spans nested in it cover (a child that runs past its parent's end counts
  only up to that end). The benchmark's ``bench.`` spans are not children
  here: a ``bench.`` wrapper inside a program span counts with it;
* ``total_s`` and ``count``: inclusive time and number of spans;
* ``args``: each numeric arg or count summed;
* ``idle_s``: device idle time, each instant of a gap put down to the
  innermost program span over it, else the innermost ``bench.`` span,
  averaged over the devices.

The ``Tracer`` stops only at the end of a unit of work, so every root
(``restore``, ``serve.step_batch``) in the window is whole. A trace with
no program span, as the program wrote before it had them, reads as None.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

from benchlib import spec, trace
from benchlib.cell import WINDOW_SPAN

PROGRAM = re.compile(r"^(restore|blockstore|serve)(\.|$)")
#: Where the benchmark's runs write, one directory per workload.
BENCH_RUNS = os.path.join(spec.ROOT, "runs", "bench")


@dataclass
class ProgramSpans:
    window: tuple[float, float]  # ns on the trace's clock
    self_s: dict[str, float]
    total_s: dict[str, float]
    count: dict[str, int]
    args: dict[str, dict[str, float]]
    idle_s: dict[str, float]


def segments(spans: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """The time the spans of one thread cover, cut into pieces, each named
    by the innermost span over it, in order. A child that runs past its
    parent's end keeps its own time, and its parent's ends there."""
    out: list[tuple[float, float, str]] = []
    # [end, name, cursor (accounted up to here), reach (latest end below)]
    stack: list[list] = []

    def pop():
        end, name, cur, reach = stack.pop()
        if end > cur:
            out.append((cur, end, name))
        if stack:
            parent = stack[-1]
            reach = max(end, reach)
            parent[2] = max(parent[2], min(reach, parent[0]))
            parent[3] = max(parent[3], reach)

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and max(stack[-1][0], stack[-1][3]) <= s:
            pop()
        if stack:
            top = stack[-1]
            if s > top[2] and top[0] > top[2]:
                out.append((top[2], min(s, top[0]), top[1]))
            top[2] = max(top[2], s)
        stack.append([e, name, s, e])
    while stack:
        pop()
    return sorted(out)


def self_times(spans: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per name, the time of each span of one thread less the part of it
    its children cover, clipped to the span (nanoseconds)."""
    out = {name: 0.0 for *_, name in spans}
    for s, e, name in segments(spans):
        out[name] += e - s
    return out


def _overlap(pieces: list[tuple[float, float, str]], lo: float, hi: float):
    """The parts of the sorted, disjoint ``pieces`` inside [lo, hi]."""
    i = bisect.bisect_left(pieces, (lo,)) - 1
    for s, e, name in pieces[max(i, 0):]:
        if s >= hi:
            break
        if e > lo:
            yield max(s, lo), min(e, hi), name


def from_profile(profile) -> ProgramSpans | None:
    """``profile`` is a ``jax.profiler.ProfileData``; None where it holds no
    program span."""
    lines: list[list[tuple[float, float, str, dict]]] = []
    bench: list[tuple[float, float, str]] = []
    device_ops: dict[int, list[tuple[float, float]]] = defaultdict(list)
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[int(m.group(1))] += [
                        (e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ours = []
                for e in line.events:
                    if PROGRAM.match(e.name):
                        ours.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                     dict(e.stats)))
                    elif e.name.startswith(trace.SPAN_PREFIX):
                        bench.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                if ours:
                    lines.append(ours)
    if not lines:
        return None
    win = [(s, e) for s, e, n in bench if n == WINDOW_SPAN]
    if win:
        lo, hi = min(win)
    else:  # a trace the benchmark did not take: all of it
        lo = min(s for ours in lines for s, *_ in ours)
        hi = max(e for ours in lines for _, e, *_ in ours)
    self_s: dict[str, float] = defaultdict(float)
    total_s: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    args: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    ours_pieces: list[tuple[float, float, str]] = []
    for ours in lines:
        kept = [x for x in ours if x[0] >= lo and x[1] <= hi]
        for s, e, name, stats in kept:
            total_s[name] += (e - s) * 1e-9
            count[name] += 1
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    args[name][k] += v
        pieces = segments([x[:3] for x in kept])
        for s, e, name in pieces:
            self_s[name] += (e - s) * 1e-9
        ours_pieces += pieces
    ours_pieces.sort()
    bench_pieces = segments([x for x in bench if x[0] < hi and x[1] > lo])
    idle: dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        clipped = [(max(s, lo), min(e, hi)) for s, e in ops if e > lo and s < hi]
        share = 1e-9 / len(device_ops)
        for s, e in trace.gaps(clipped, lo, hi):
            # each instant of a gap goes to the innermost program span over
            # it, else the innermost bench. span, else to no span
            covered = sorted((a, b) for a, b, _ in _overlap(ours_pieces, s, e))
            for a, b, name in _overlap(ours_pieces, s, e):
                idle[name] += (b - a) * share
            for a, b in trace.gaps(covered, s, e):
                rest = b - a
                for c, d, name in _overlap(bench_pieces, a, b):
                    idle[name] += (d - c) * share
                    rest -= d - c
                if rest > 0:
                    idle[trace.NO_SPAN] += rest * share
    return ProgramSpans((lo, hi), dict(self_s), dict(total_s), dict(count),
                        {k: dict(v) for k, v in args.items()}, dict(idle))


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramSpans | None:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def trace_file(workload: str) -> str | None:
    found = glob.glob(os.path.join(BENCH_RUNS, workload, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return found[0] if found else None


def per_root(run, root: str, value) -> float | None:
    """``value(spans)`` over the number of ``root`` spans in the run's
    traced window: None without a trace, or where it holds no ``root``."""
    if run.trace is None:
        return None
    path = trace_file(run.workload)
    spans = load(path) if path else None
    if spans is None or not spans.count.get(root):
        return None
    return value(spans) / spans.count[root]


def per_start(run, value) -> float | None:
    """Mean per cold start (``restore`` root) of ``value(spans)``."""
    return per_root(run, "restore", value)


def per_batch(run, value) -> float | None:
    """Mean per batch (``serve.step_batch`` root) of ``value(spans)``."""
    return per_root(run, "serve.step_batch", value)
