"""Host time of the restore's host-to-device copies: self time of the
program's span ``restore.h2d`` (the ``jnp.asarray`` of each decoded leaf),
mean per cold start (``restore`` span) of the traced window, in s. A copy
still in flight when ``jnp.asarray`` returns is waited for later, under
``serve.first_token`` (``token_wait_s.cold``)."""
from benchlib import program_spans


def read(run):
    return program_spans.per_start(run, lambda s: s.self_s.get("restore.h2d", 0.0))
