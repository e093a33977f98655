"""The cold-start runner, run on the CPU at a tiny size through everything
a run does after its look for a chip: a sound run comes out correct; the
control (the plain reference with fp8 products, put in the program's
place) and runs with the timed path broken underneath come out not
correct, once for each fault the cell can have."""
import dataclasses
import os
import sys

import jax
import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(BENCH, "..", "src")
sys.path[:0] = [BENCH, os.path.join(BENCH, "reference"), os.path.join(BENCH, "tools"), SRC]

from benchlib import spec  # noqa: E402
from benchlib.cell import Cell, is_correct  # noqa: E402

# limits at this tiny size, from readings on the CPU over three seeds:
# sound runs read gaps up to 0.048 and errors 0.017-0.027, the control
# errors of 0.09-0.24
TINY = {"served_token_gap": 0.15, "first_logits_err": 0.05}


def tiny_cell(work_dir: str, seed: int = 2**31 + 11) -> Cell:
    from repro.configs import mamba2_130m

    cfg = mamba2_130m.SMOKE
    traffic = dict(spec.traffic_doc("cold_burst"), prompt_lens=[64, 128], prompt_counts=[1, 1])
    return Cell(workload="cold", cfg=cfg, sizes=dataclasses.asdict(cfg),
                reference=spec.load_module(spec.reference_path("mamba2")),
                traffic=traffic, limits={"restored_bits_differing": 0, **TINY}, seed=seed,
                seconds=1.0, trace=False, devices=jax.devices(), work_dir=work_dir)


def control_correct(cell, out) -> bool:
    from control import control_outcome  # bench/tools/control.py

    ctl = control_outcome(cell, out)
    return is_correct(ctl)


def alter_first_token(monkeypatch):
    """Fault: the model puts a wrong token first, in the prefill."""
    import repro.serving.engine as engine
    from repro.models import model_for

    def altered(cfg):
        base = model_for(cfg)

        def prefill(params, batch, cache_len=None):
            lg, cache = base.prefill(params, batch, cache_len=cache_len)
            wrong = (lg[0, -1].argmax() + 1) % lg.shape[-1]
            return lg.at[0, -1, wrong].set(lg[0, -1].max() + 100.0), cache

        return dataclasses.replace(base, prefill=prefill)

    monkeypatch.setattr(engine, "model_for", altered)


def test_cold_start_sound_control_and_faults(tmp_path, monkeypatch):
    from benchlib.serve import cold_start
    from repro.checkpoint.manager import CheckpointManager

    cell = tiny_cell(str(tmp_path))
    out = cold_start(cell)
    assert is_correct(out), out.checks
    assert out.checks["restored_bits_differing"][0] == 0
    assert out.attempted >= 1 and out.failed == 0 and out.e2e["cold_ttft_s"] > 0
    assert not control_correct(cell, out)

    # fault: a first token altered where the model produces it
    with monkeypatch.context() as m:
        alter_first_token(m)
        bad = cold_start(tiny_cell(str(tmp_path)))
    assert not is_correct(bad) and bad.checks["served_token_gap"][0] > TINY["served_token_gap"]

    # fault: a restored weight altered where the restore produces it
    decode = CheckpointManager._decode

    def corrupt(self, meta, raw):
        arr = decode(self, meta, raw)
        return arr.at[(0,) * arr.ndim].add(1.0) if meta.path.endswith("table") else arr

    with monkeypatch.context() as m:
        m.setattr(CheckpointManager, "_decode", corrupt)
        bad = cold_start(tiny_cell(str(tmp_path)))
    assert not is_correct(bad) and bad.checks["restored_bits_differing"][0] >= 1
