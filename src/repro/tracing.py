"""The program's own spans and counters, on the profiler's clock.

``with span("restore.h2d", bytes=n) as sp:`` opens a
``jax.profiler.TraceAnnotation``, so the span lands in the same
``.xplane.pb`` as the device's ops whenever a profiler runs, and costs a
few microseconds of host time, bookkeeping included, when none does.
``sp.set(**counts)`` adds numbers known only when the work ends; they are
written as the event's metadata at exit. After exit ``sp.seconds`` is
the span's host time (``time.perf_counter``), and ``sp.totals`` sums
every number that the span and the spans nested in it carried, by
``(span name, key)``, with each span's ``seconds``: a caller reads its
counters from there and measures each interval once.

JAX's compile events are counted on the innermost open span of the
thread that raised them: ``traces`` (jaxprs traced), ``cache_loads``
(executables loaded from the persistent cache) and ``compiles`` (backend
compile requests less those loads). ``layer_span`` opens a span around
one layer of a model: timed, annotated and summed like any other, but it
takes no compile counts, which go on to the span around it, so that a
caller's counts (``serve.prefill``'s) do not depend on how finely the
model is spanned. Inside ``jit`` or a ``lax.scan`` body a layer's span
covers its tracing and records once per trace, not once per run.

JAX is imported at the first span, not with this module, and where it
cannot be imported a span is timed and counted all the same, with no
annotation: the block store opens spans, and the host-only fleet plane
imports it on interpreters without JAX.
"""
from __future__ import annotations

import threading
import time

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_local = threading.local()
_first_use = threading.Lock()
_annotation = None  # jax.profiler.TraceAnnotation, once the listeners are in


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _count(key: str, n: int = 1) -> None:
    for sp in reversed(getattr(_local, "stack", None) or ()):
        if sp.takes_counts:
            sp.counts[key] = sp.counts.get(key, 0) + n
            return


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event == _TRACE_EVENT:
        _count("traces")
    elif event == _COMPILE_EVENT:
        _count("compiles")


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _count("cache_loads")
        _count("compiles", -1)


class _NoAnnotation:
    """Stands in for ``TraceAnnotation`` where JAX is not installed."""

    def __init__(self, name: str, **args) -> None:
        pass

    def __enter__(self) -> None:
        pass

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **counts) -> None:
        pass


def _listen() -> None:
    global _annotation
    with _first_use:
        if _annotation is None:
            try:
                import jax
            except ImportError:
                _annotation = _NoAnnotation
                return
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _annotation = jax.profiler.TraceAnnotation


class Span:
    """One span of the program; see the module's docstring."""

    __slots__ = ("name", "args", "takes_counts", "counts", "totals", "seconds",
                 "_tm", "_t0")

    def __init__(self, name: str, args: dict, takes_counts: bool = True) -> None:
        self.name = name
        self.args = args
        self.takes_counts = takes_counts
        self.counts: dict = {}
        self.totals: dict[tuple[str, str], float] = {}
        self.seconds: float | None = None

    def set(self, **counts) -> None:
        self.counts.update(counts)

    def __enter__(self) -> "Span":
        if _annotation is None:
            _listen()
        self._tm = _annotation(self.name, **self.args)
        self._tm.__enter__()
        _stack().append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        stack = _local.stack
        stack.pop()
        if self.counts:
            self._tm.set_metadata(**self.counts)
        self._tm.__exit__(*exc)
        totals, name = self.totals, self.name
        for d in (self.args, self.counts):
            for k, v in d.items():
                if type(v) in (int, float):  # not a label, nor a bool
                    totals[name, k] = totals.get((name, k), 0) + v
        totals[name, "seconds"] = totals.get((name, "seconds"), 0) + self.seconds
        if stack:
            parent = stack[-1].totals
            for key, v in totals.items():
                parent[key] = parent.get(key, 0) + v


def span(name: str, **args) -> Span:
    """A span named ``name`` carrying ``args``, to be used in ``with``."""
    return Span(name, args)


def layer_span(name: str, **args) -> Span:
    """A span around one layer of a model; compile events inside it count
    on the enclosing span (see the module's docstring)."""
    return Span(name, args, takes_counts=False)


def total(totals: dict, key: str) -> float:
    """``key`` summed over every span name in ``totals``."""
    return sum(v for (_, k), v in totals.items() if k == key)
