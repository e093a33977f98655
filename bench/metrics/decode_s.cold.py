"""Host time the restore spends turning blocks into leaves: self time of
the program's spans ``blockstore.read_range`` (joining a leaf's blocks)
and ``restore.decode`` (``frombuffer``/``view``/``reshape``), mean per cold
start (``restore`` span) of the traced window, in s."""
from benchlib import program_spans


def read(run):
    return program_spans.per_start(
        run, lambda s: s.self_s.get("blockstore.read_range", 0.0)
        + s.self_s.get("restore.decode", 0.0))
