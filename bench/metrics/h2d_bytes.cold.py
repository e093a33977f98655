"""Bytes the restore hands to the device: the ``bytes`` of the program's
spans ``restore.h2d`` summed, mean per cold start (``restore`` span) of the
traced window."""
from benchlib import program_spans


def read(run):
    return program_spans.per_start(
        run, lambda s: s.args.get("restore.h2d", {}).get("bytes", 0))
