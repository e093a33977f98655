"""Host time from the end of the prefill's dispatch until the first tokens
are on the host: self time of the program's span ``serve.first_token``
(argmax of the last position and the host pull), mostly the wait for the
device and for any host-to-device copy still in flight, mean per batch
(``serve.step_batch`` span) of the traced window, in s."""
from benchlib import program_spans


def read(run):
    return program_spans.per_batch(
        run, lambda s: s.self_s.get("serve.first_token", 0.0))
