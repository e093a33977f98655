"""Host time of a cold start's first ``step_batch`` (the span
``bench.step_batch``): prefill of the burst until its first tokens are on
the host, mean per cold start of the window, in s."""


def read(run):
    starts = run.host.get("cold_starts")
    return sum(s["first_batch_s"] for s in starts) / len(starts) if starts else None
