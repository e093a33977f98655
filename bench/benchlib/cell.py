"""What a runner is given for one run, what it hands back, and the pieces
every runner shares: the traced window and the count of compilations."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any

import jax

WINDOW_SPAN = "bench.window"


@dataclass
class Cell:
    workload: str
    cfg: Any  # the program's ModelConfig
    sizes: dict  # the configuration file's "model" object
    reference: Any  # the plain reference module
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    work_dir: str  # inside the checkout; emptied at the start of a run


@dataclass
class Outcome:
    e2e: dict[str, float]  # end-to-end metrics by name
    host: dict  # what the per-layer readers read
    checks: dict[str, tuple[float, float]]  # name -> (value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    window_start: float  # time.monotonic() when the window opened
    trace_file: str | None = None
    compared: Any = None  # the requests compared (benchlib.serve.Compared)
    notes: dict = field(default_factory=dict)


def is_correct(out: Outcome) -> bool:
    """Every request answered and every number compared within its limit."""
    return out.failed == 0 and all(v <= lim for v, lim in out.checks.values())


@dataclass
class Run:
    """What a per-layer metric's reader reads: ``read(run)`` returns a
    number, or None where the run holds nothing to read it from."""
    workload: str
    sizes: dict
    traffic: dict
    peaks: dict
    chips: int
    e2e: dict[str, float]
    host: dict
    trace: Any  # benchlib.trace.Reduction, or None without a trace


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts, by time, the traces, the executables loaded from the
    persistent cache and those compiled, so that a run shows what its
    window traced or compiled."""

    def __init__(self) -> None:
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.events.append((time.monotonic(), "traces"))
        elif name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), "requests"))

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.events.append((time.monotonic(), "loaded"))

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, t0: float, t1: float) -> dict[str, int]:
        n = {"traces": 0, "requests": 0, "loaded": 0}
        for t, kind in self.events:
            if t0 <= t <= t1:
                n[kind] += 1
        return {"traces": n["traces"], "loaded_from_cache": n["loaded"],
                "compiled": n["requests"] - n["loaded"]}


class Tracer:
    """The profiler over the first ``trace_seconds`` of a window, inside a
    host span named ``bench.window``. ``tick`` is called at the end of each
    unit of work; the trace stops at the first tick past the limit, so it
    holds whole units. The time spent stopping it is kept, so that rates
    over the window can leave it out."""

    def __init__(self, on: bool, trace_seconds: float, out_dir: str) -> None:
        self.on = on
        self.limit = trace_seconds
        self.dir = out_dir
        self.active = False
        self.t_start = self.t_stop = None
        self.stop_cost = 0.0
        self._span = None

    def start(self) -> None:
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.active = True
        self.t_start = time.monotonic()

    def tick(self) -> None:
        if self.active and time.monotonic() - self.t_start >= self.limit:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        self.t_stop = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.stop_cost = time.monotonic() - self.t_stop

    def file(self) -> str | None:
        if not self.on:
            return None
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        return found[0] if found else None


def memory_peak(devices: list) -> int:
    """Peak bytes in use on the fullest of ``devices``, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0
