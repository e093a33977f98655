"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* the traced window: the host span the benchmark opened around it;
* each device's busy time: the union of its ``XLA Ops`` intervals, clipped
  to the window; idle is the rest;
* device time per operation, by a stable name: the HLO instruction's name
  without its numeric suffix (``%flash_attention_bhtd.1 = ...`` is
  ``flash_attention_bhtd``, ``%fusion.12 = ...`` is ``fusion``), counting
  an event's own time, not that of the events nested in it;
* each idle gap attributed to the innermost ``bench.`` host span that
  holds its midpoint;
* each device's program executions (``XLA Modules``).

Device and host events share the trace's clock to within about a
millisecond, so an attribution is sound for gaps longer than that.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
NO_SPAN = "(no bench span)"
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
_SHAPE = re.compile(r"=\s*\(?(\w+)\[([\d,]*)\]")


def stable_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    if m:
        return m.group(1)
    return re.sub(r"(\.\d+)+$", "", event_name.split(" ", 1)[0].lstrip("%"))


def out_shape(event_name: str) -> tuple[str, tuple[int, ...]] | None:
    """The dtype and dimensions of an HLO op's (first) result, from the
    event name ``%name = bf16[8,256,128]{...} op(...)``."""
    m = _SHAPE.search(event_name)
    if not m:
        return None
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float):
    """The parts of [lo, hi] that no interval covers, in order."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per stable name, the time of each event less that of the events it
    encloses (nanoseconds)."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    stack: list[list] = []  # [end, name, child_time, dur]
    out: dict[str, float] = defaultdict(float)

    def close(item):
        out[item[1]] += item[3] - item[2]

    for s, e, name in evs:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return dict(out)


@dataclass
class Device:
    index: int
    ops: list[tuple[float, float, str]] = field(default_factory=list)  # ns, stable name
    # custom calls (kernels) with their result: (start, end, name, dtype, dims)
    kernels: list[tuple] = field(default_factory=list)


@dataclass
class Reduction:
    window: tuple[float, float]  # ns on the trace's clock
    devices: dict[int, Device]
    spans: list[tuple[float, float, str]]  # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, ivs):
        lo, hi = self.window
        return [(max(s, lo), min(e, hi)) for s, e, *_ in ivs if e > lo and s < hi]

    def busy_s(self, device: int) -> float:
        return union_length(self._clipped(self.devices[device].ops)) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices (a fraction)."""
        return 1.0 - self.mean_busy_s() / self.window_s

    def op_seconds(self) -> dict[str, float]:
        """Self time per stable op name inside the window, averaged over
        the devices."""
        lo, hi = self.window
        tot: dict[str, float] = defaultdict(float)
        for dev in self.devices.values():
            inside = [(s, e, n) for s, e, n in dev.ops if s >= lo and e <= hi]
            for name, ns in self_times(inside).items():
                tot[name] += ns * 1e-9 / len(self.devices)
        return dict(tot)

    def kernel_events(self, pattern: str) -> dict[int, list[tuple]]:
        """Per device, the kernel (custom call) events inside the window
        whose name matches: (seconds, dtype, result dims)."""
        lo, hi = self.window
        rx = re.compile(pattern)
        return {i: [((e - s) * 1e-9, dt, dims) for s, e, n, dt, dims in dev.kernels
                    if s >= lo and e <= hi and rx.search(n)]
                for i, dev in self.devices.items()}

    def span_at(self, t: float) -> str:
        best = None
        for s, e, n in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else NO_SPAN

    def idle_by_span(self) -> dict[str, float]:
        """Idle seconds by the host span they fall in, averaged over the
        devices."""
        lo, hi = self.window
        out: dict[str, float] = defaultdict(float)
        for dev in self.devices.values():
            for s, e in gaps(self._clipped(dev.ops), lo, hi):
                out[self.span_at((s + e) / 2)] += (e - s) * 1e-9 / len(self.devices)
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda x: -x[1])[:top]
        idle = sorted(self.idle_by_span().items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def reduce_xspace(profile, window_span: str | None) -> Reduction:
    """``profile`` is a ``jax.profiler.ProfileData``. The window is the
    host span named ``window_span`` (its first occurrence), or with None
    from the first ``bench.`` span's start to the last one's end."""
    devices: dict[int, Device] = {}
    spans: list[tuple[float, float, str]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device(int(m.group(1))))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name = stable_name(e.name)
                        dev.ops.append((e.start_ns, e.start_ns + e.duration_ns, name))
                        if "custom-call(" in e.name:
                            shape = out_shape(e.name) or ("", ())
                            dev.kernels.append((e.start_ns, e.start_ns + e.duration_ns,
                                                name) + shape)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    win = [(s, e) for s, e, n in spans if window_span is None or n == window_span]
    if not win:
        raise ValueError(f"the trace holds no host span {window_span or SPAN_PREFIX!r}")
    if window_span is None:
        return Reduction((min(s for s, _ in win), max(e for _, e in win)), devices, spans)
    return Reduction(min(win), devices, spans)


def reduce_file(path: str, window_span: str | None) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_xspace(ProfileData.from_file(path), window_span)
