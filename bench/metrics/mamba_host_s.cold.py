"""Host time of a cold start's prefill in its Mamba-2 sublayers (in_proj,
conv, SSD, gated norm, out_proj, and the conv tails and SSM state
returned): self time of the program's layer spans ``model.mamba``, mean
per cold start (``restore`` span) of the traced window, in s. With
``attn_host_s.cold`` and ``mlp_host_s.cold`` it splits
``prefill_host_s.cold``. None where the program opens no such span."""
from benchlib import model_spans


def read(run):
    return model_spans.layer_self_s(run, "model.mamba")
