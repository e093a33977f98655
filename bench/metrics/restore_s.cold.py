"""Restore time of a cold start: host time of ``ServeEngine.start(lazy=True)``
(the span ``bench.start``), mean per cold start of the window, in s."""


def read(run):
    starts = run.host.get("cold_starts")
    return sum(s["restore_s"] for s in starts) / len(starts) if starts else None
