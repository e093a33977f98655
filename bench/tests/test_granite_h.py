"""Granite 4.0-H (``repro.configs.granite4_h_micro``) against its plain
reference (``bench/reference/granite_h.py``) at the ``SMOKE`` size on the
CPU: one whole period of the layer pattern (nine Mamba-2 layers, attention
at index 5, a SwiGLU MLP in each), bfloat16 weights drawn by the
benchmark's ``weights.py``. Logits are compared, not tokens.

Tolerances, on the relative 2-norm error of a position's logits over the
vocabulary: the program computes in bfloat16 (8 significant bits) through
ten layers, and read a mean over positions of 0.021-0.022 and a largest
single position of 0.042-0.053 on three seeds (CPU); the reference with
every weight product's operands rounded to float8 e4m3, the next precision
down, read means of 0.15-0.16 and no position below 0.084. So a mean of
0.04 and a largest position of 0.1 pass the program with room and fail
the control by its mean.
"""
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(BENCH, "..", "src")
sys.path[:0] = [BENCH, os.path.join(BENCH, "reference"), os.path.join(BENCH, "tools"), SRC]

from benchlib import check, spec, weights  # noqa: E402
from benchlib.cell import Cell, is_correct  # noqa: E402
from benchlib.weights import program_shapes  # noqa: E402

from repro.configs import granite4_h_micro  # noqa: E402
from repro.models import model_for  # noqa: E402

CFG = granite4_h_micro.SMOKE
SIZES = dataclasses.asdict(CFG)
REF = spec.load_module(spec.reference_path("granite_h"))
MEAN_ERR, MAX_ERR = 0.04, 0.1
SEED = 2**31 + 11
# the cold runner's limits at this size, from CPU readings on four seeds:
# sound runs read first-logits errors of 0.016-0.028 and served tokens at
# the reference's argmax (gap 0); the control reads errors of 0.12-0.15.
# Logits here are small (divided by 8; standard deviation about 0.02), so
# a token served one place off the argmax lies a few hundredths below it
TINY = {"served_token_gap": 0.005, "first_logits_err": 0.06}
_make_params = weights.make_params


@functools.lru_cache(maxsize=None)
def drawn(seed: int):
    """The benchmark's weights for ``seed``, drawn once: ``make_params``
    jits a new closure at each call, and its 200 leaves take about 20 s to
    compile on the CPU."""
    return _make_params(program_shapes(CFG), seed)


@pytest.fixture
def cached_draws(monkeypatch):
    """``make_params`` of this model answered from ``drawn``."""
    def make_params(like, seed, **kw):
        if not kw and like == program_shapes(CFG):
            return drawn(seed)
        return _make_params(like, seed, **kw)

    monkeypatch.setattr(weights, "make_params", make_params)
    import benchlib.serve

    monkeypatch.setattr(benchlib.serve, "make_params", make_params)


def rel_err(a, ref):
    a = jnp.asarray(a, jnp.float32)
    return np.asarray(jnp.linalg.norm(a - ref, axis=-1) / jnp.linalg.norm(ref, axis=-1))


def reference_logits(params, toks, quant=None):
    return REF.logits(params, REF.hidden(params, toks, SIZES, quant), SIZES, quant)


def tokens(seed, batch, length):
    rng = np.random.default_rng(seed % 2**32)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (batch, length)), jnp.int32)


# several SSD and attention chunks of 16 with a partial last one; exactly two
@pytest.mark.parametrize("length", [70, 32])
def test_prefill_logits_match_the_reference(length):
    params = drawn(SEED)
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype(jnp.bfloat16)}
    toks = tokens(length, 2, length)
    logits, _ = model_for(CFG).prefill(params, {"tokens": toks})
    err = rel_err(logits, reference_logits(params, toks))
    assert err.mean() < MEAN_ERR and err.max() < MAX_ERR, (err.mean(), err.max())


@pytest.mark.parametrize("t", [37, 16])
def test_decode_through_the_caches_matches_the_reference(t):
    """Prefill t tokens, then decode the next k through the KV cache of
    the attention layer and the conv and SSM states of the Mamba-2 layers;
    each step's logits against the reference's full forward pass."""
    model = model_for(CFG)
    params = drawn(SEED)
    k = 4
    seq = tokens(t, 2, t + k)
    logits, cache = model.prefill(params, {"tokens": seq[:, :t]}, cache_len=t + k)
    steps = [logits[:, -1]]
    for i in range(k):
        out, cache = model.decode_step(
            params, {"tokens": seq[:, t + i:t + i + 1], "pos": jnp.asarray(t + i, jnp.int32)},
            cache)
        steps.append(out[:, 0])
    ref = reference_logits(params, seq)[:, t - 1:]
    err = rel_err(jnp.stack(steps, axis=1), ref)
    assert err.mean() < MEAN_ERR and err.max() < MAX_ERR, (err.mean(), err.max())


def test_the_fp8_control_fails_the_tolerance():
    params = drawn(SEED)
    toks = tokens(SEED, 2, 70)
    err = rel_err(reference_logits(params, toks, "fp8"), reference_logits(params, toks))
    assert err.mean() > MEAN_ERR, err.mean()


def test_lazy_cold_start_through_the_engine(tmp_path):
    """A lazy restore through ``ServeEngine`` holds the full restore's tree,
    bit for bit, and its first batch's logits match the reference."""
    from benchlib.serve import BatchClock, instrument
    from repro.checkpoint.manager import CheckpointManager
    from repro.serving.engine import ServeEngine

    like = program_shapes(CFG)
    params = drawn(SEED)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, params)
    prompt = np.asarray(tokens(SEED, 1, 50)[0])
    eng = ServeEngine(CFG, max_batch=1)
    clock = BatchClock()
    instrument(eng, clock)
    eng.submit(prompt, 1)
    eng.start(mgr, 0, like, lazy=True)
    assert eng.cold_start_stats["h2d_bytes"] == mgr.block_manifest(0).raw_size
    (req,) = eng.step_batch()
    assert check.tree_mismatches(eng.params, mgr.restore(0, like)) == 0
    assert check.tree_mismatches(eng.params, params) == 0
    ref = reference_logits(params, jnp.asarray(prompt)[None])[0, -1]
    assert rel_err(clock.first_logits[0], ref) < MAX_ERR
    assert float(ref.max() - ref[req.out_tokens[0]]) <= TINY["served_token_gap"]


def tiny_cell(work_dir: str, seed: int = SEED) -> Cell:
    traffic = dict(spec.traffic_doc("cold_burst"), prompt_lens=[64, 128], prompt_counts=[1, 1])
    return Cell(workload="cold", cfg=CFG, sizes=SIZES, reference=REF, traffic=traffic,
                limits={"restored_bits_differing": 0, **TINY}, seed=seed, seconds=1.0,
                trace=False, devices=jax.devices(), work_dir=work_dir)


def test_cold_start_runner_sound_control_and_faults(tmp_path, monkeypatch, cached_draws):
    """The benchmark's cold runner on this model: a sound run is correct;
    the control, a served token off the reference's argmax and a restored
    weight altered are not."""
    from control import control_outcome  # bench/tools/control.py
    from benchlib.serve import cold_start
    from repro.checkpoint.manager import CheckpointManager
    import repro.serving.engine as engine

    cell = tiny_cell(str(tmp_path))
    out = cold_start(cell)
    assert is_correct(out), out.checks
    assert out.checks["restored_bits_differing"][0] == 0 and out.failed == 0
    assert not is_correct(control_outcome(cell, out))

    def altered(cfg):  # the model puts the next token after its best first
        base = model_for(cfg)

        def prefill(params, batch, cache_len=None):
            lg, cache = base.prefill(params, batch, cache_len=cache_len)
            wrong = (lg[0, -1].argmax() + 1) % lg.shape[-1]
            return lg.at[0, -1, wrong].set(lg[0, -1].max() + 1.0), cache

        return dataclasses.replace(base, prefill=prefill)

    with monkeypatch.context() as m:
        m.setattr(engine, "model_for", altered)
        bad = cold_start(tiny_cell(str(tmp_path)))
    assert not is_correct(bad) and bad.checks["served_token_gap"][0] > TINY["served_token_gap"]

    decode = CheckpointManager._decode

    def corrupt(self, meta, raw):
        arr = decode(self, meta, raw)
        return arr.at[(0,) * arr.ndim].add(1.0) if meta.path.endswith("table") else arr

    with monkeypatch.context() as m:
        m.setattr(CheckpointManager, "_decode", corrupt)
        bad = cold_start(tiny_cell(str(tmp_path)))
    assert not is_correct(bad) and bad.checks["restored_bits_differing"][0] >= 1
