"""Traces and executables loaded from the persistent cache during a cold
start's prefill: the ``traces`` and ``cache_loads`` counts of the program's
span ``serve.prefill`` summed, mean per batch (``serve.step_batch`` span) of
the traced window."""
from benchlib import program_spans


def read(run):
    def counts(s):
        a = s.args.get("serve.prefill", {})
        return a.get("traces", 0) + a.get("cache_loads", 0)

    return program_spans.per_batch(run, counts)
