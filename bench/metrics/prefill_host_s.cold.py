"""Host time of a cold start's prefill: self time of the program's span
``serve.prefill`` (the eager ``model.prefill`` call: tracing, loading
executables and dispatch; the benchmark's ``bench.prefill`` wrapper inside
it counts with it), mean per batch (``serve.step_batch`` span) of the traced
window, in s."""
from benchlib import program_spans


def read(run):
    return program_spans.per_batch(run, lambda s: s.self_s.get("serve.prefill", 0.0))
