"""Host time the restore spends decompressing blocks: self time of the
program's span ``blockstore.decompress`` (the ``_ingest`` loop of one
contiguous run of blocks), mean per cold start (``restore`` span) of the
traced window, in s."""
from benchlib import program_spans


def read(run):
    return program_spans.per_start(
        run, lambda s: s.self_s.get("blockstore.decompress", 0.0))
