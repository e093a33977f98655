"""Host time of a cold start's prefill in its attention sublayers
(projections, softmax attention, the KV cache returned): self time of the
program's layer spans ``model.attn``, mean per cold start (``restore``
span) of the traced window, in s. With ``mamba_host_s.cold`` and
``mlp_host_s.cold`` it splits ``prefill_host_s.cold``, in whose span they
nest. None where the program opens no such span."""
from benchlib import model_spans


def read(run):
    return model_spans.layer_self_s(run, "model.attn")
