"""Host time the restore spends reading the checkpoint file: self time of
the program's span ``blockstore.read`` (``BlockReader._read_at``), mean per
cold start (``restore`` span) of the traced window, in s."""
from benchlib import program_spans


def read(run):
    return program_spans.per_start(run, lambda s: s.self_s.get("blockstore.read", 0.0))
