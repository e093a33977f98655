"""Bytes of attention keys and values in the cache a cold start's prefill
returns (``cache_len`` = prompt + new tokens slots in each attention
layer): the ``kv_bytes`` count of the program's span ``serve.prefill``
summed, mean per cold start (``restore`` span) of the traced window. None
where the span carries no such count."""
from benchlib import model_spans


def read(run):
    def kv(spans, _path):
        prefill = spans.args.get("serve.prefill", {})
        return prefill.get("kv_bytes")

    return model_spans.per_start(run, kv)
