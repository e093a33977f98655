"""The model's layer spans in a run's trace, for the per-layer readers.

``repro.models.transformer`` opens ``model.attn``, ``model.mamba`` and
``model.mlp`` around each sublayer (``repro.tracing.layer_span``, with
``tokens``). Run eagerly, as the engine's prefill is, an unstacked layer
records a span per call; a layer inside a ``lax.scan`` or ``jit`` body
records one per trace. ``benchlib.program_spans`` reads only the
``restore``, ``blockstore`` and ``serve`` spans, so a layer span's time
counts there in the self time of ``serve.prefill`` around it; here each
layer kind's self time is read on its own, inside ``bench.window``, and
put per cold start (``restore`` spans). A trace with no layer span, as the
program wrote before it had them, reads as None.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict

from benchlib import program_spans

LAYER = re.compile(r"^model\.")


@functools.lru_cache(maxsize=4)
def self_seconds(path: str, window: tuple[float, float]) -> dict[str, float]:
    """Per layer span name, its self time (s) inside ``window`` (ns)."""
    from jax.profiler import ProfileData

    lo, hi = window
    out: dict[str, float] = defaultdict(float)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ours = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events
                    if LAYER.match(e.name)]
            ours = [x for x in ours if x[0] >= lo and x[1] <= hi]
            for name, ns in program_spans.self_times(ours).items():
                out[name] += ns * 1e-9
    return dict(out)


def spans_of(run):
    """The run's program spans (``benchlib.program_spans``) and the path of
    its trace, or None without a trace or a ``restore`` span."""
    if run.trace is None:
        return None
    path = program_spans.trace_file(run.workload)
    spans = program_spans.load(path) if path else None
    if spans is None or not spans.count.get("restore"):
        return None
    return spans, path


def per_start(run, value) -> float | None:
    """``value(spans, path)`` over the number of cold starts (``restore``
    spans) in the traced window; None where ``value`` finds nothing."""
    found = spans_of(run)
    if found is None:
        return None
    v = value(*found)
    return None if v is None else v / found[0].count["restore"]


def layer_self_s(run, name: str) -> float | None:
    """Self time of the layer span ``name`` per cold start, in s."""
    return per_start(run, lambda spans, path: self_seconds(path, spans.window).get(name))
