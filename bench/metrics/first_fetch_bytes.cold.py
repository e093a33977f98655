"""Compressed bytes the on-demand restore fetched before the first leaves
were in place (``cold_start_stats["first_fetch_compressed_bytes"]``), mean
per cold start of the window."""


def read(run):
    starts = run.host.get("cold_starts")
    return sum(s["first_fetch_bytes"] for s in starts) / len(starts) if starts else None
