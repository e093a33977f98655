"""Host time of a cold start's prefill in its SwiGLU MLP sublayers: self
time of the program's layer spans ``model.mlp``, mean per cold start
(``restore`` span) of the traced window, in s. With ``attn_host_s.cold``
and ``mamba_host_s.cold`` it splits ``prefill_host_s.cold``. None where
the program opens no such span."""
from benchlib import model_spans


def read(run):
    return model_spans.layer_self_s(run, "model.mlp")
