"""The cold-start runner, on the program's ``ServeEngine``.

``cold_start`` (traffic ``runner: "benchlib.serve:cold_start"``): a block
checkpoint of the seed's weights is written in set-up; the window then
runs cold starts back to back, one at a time. Each builds a new engine
with a burst already queued, restores lazily (``start(lazy=True)``) and
serves the burst's first tokens (``step_batch``).

Spans: ``bench.start`` and ``bench.step_batch`` around the engine's calls,
``bench.prefill`` around the model's, which is wrapped without blocking.

After the window the runner compares the served tokens of the requests it
names in ``Outcome.compared`` with the plain reference, each request on
its own prompt (``compare``).
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import check
from benchlib.cell import Cell, CompileCounter, Outcome, Tracer, log, memory_peak
from benchlib.traffic import RequestStream, rng_for
from benchlib.weights import make_params, program_shapes


class BatchClock:
    """The prefill's last-position logits of one batch, kept on the device
    and not awaited."""

    def __init__(self) -> None:
        self.first_logits = None


def instrument(eng, clock: BatchClock) -> None:
    """Wrap the engine's model so each prefill is spanned and its
    last-position logits kept."""
    base = eng.model

    def prefill(params, batch, cache_len=None):
        with jax.profiler.TraceAnnotation("bench.prefill"):
            logits, cache = base.prefill(params, batch, cache_len=cache_len)
        clock.first_logits = logits[:, -1]
        return logits, cache

    eng.model = dataclasses.replace(base, prefill=prefill)


def reference_fns(reference, sizes: dict):
    """Jitted reference readings over blocks of rows ``seqs (n, L)`` at
    served positions ``pos (n, w)``; each returns the gaps ``(n, w)`` and
    the relative error ``(n,)`` of the logits at each row's first served
    position, ``|first - ref| / |ref|`` (2-norms over the vocabulary).

    ``program(params, seqs, pos, tok, first)``: the gap of the served token
    ``tok[i, k]``, and the error of the program's logits ``first (n, V)``.
    ``control(params, seqs, pos)``: the same with the reference computed
    with fp8 products in the program's place: the gap of the token it puts
    first, and the error of its logits."""

    def at(params, seqs, pos, quant):
        h = reference.hidden(params, seqs, sizes, quant)
        h = jnp.take_along_axis(h, pos[..., None], axis=1)
        return reference.logits(params, h, sizes, quant)

    def rel_err(first, ref):
        d = first.astype(jnp.float32) - ref
        return jnp.linalg.norm(d, axis=-1) / jnp.linalg.norm(ref, axis=-1)

    def gaps(ref, tok):
        flat = check.served_gaps(ref.reshape(-1, ref.shape[-1]), tok.reshape(-1))
        return flat.reshape(tok.shape)

    @jax.jit
    def program(params, seqs, pos, tok, first):
        ref = at(params, seqs, pos, None)
        return gaps(ref, tok), rel_err(first, ref[:, 0])

    @jax.jit
    def control(params, seqs, pos):
        ref = at(params, seqs, pos, None)
        low = at(params, seqs, pos, "fp8")
        return gaps(ref, jnp.argmax(low, axis=-1)), rel_err(low[:, 0], ref[:, 0])

    return program, control


def served_rows(reqs: list[dict], length: int, width: int):
    """Rows for the reference, one per request: its own prompt, then its
    served tokens but the last, then zeros to ``length`` (after every
    position read, so causal layers never see them); positions of its
    served tokens and the tokens, padded to ``width`` by repeating the
    first."""
    n = len(reqs)
    seqs = np.zeros((n, length), np.int32)
    pos = np.zeros((n, width), np.int32)
    tok = np.zeros((n, width), np.int32)
    mask = np.zeros((n, width), bool)
    for i, r in enumerate(reqs):
        prompt, out = r["prompt"], r["out"]
        t = len(prompt)
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        seqs[i, :len(seq)] = seq
        m = len(out)
        pos[i, :m] = np.arange(t - 1, t - 1 + m)
        pos[i, m:] = t - 1
        tok[i, :m] = out
        tok[i, m:] = out[0]
        mask[i, :m] = True
    return seqs, pos, tok, mask


def read_compared(fn, params, compared: "Compared", control: bool = False):
    """The gaps of every served token of ``compared.requests`` and the
    error of each request's first logits, ``block`` rows at a time (fewer
    rows pad with copies of the first)."""
    reqs, block = compared.requests, compared.block
    gaps, errs = [], []
    for i in range(0, len(reqs), block):
        part = reqs[i:i + block]
        keep = len(part)
        part = part + [part[0]] * (block - keep)
        seqs, pos, tok, mask = served_rows(part, compared.length, compared.width)
        if control:
            g, e = fn(params, seqs, pos)
        else:
            first = jnp.stack([r["first_logits"][0][r["first_logits"][1]] for r in part])
            g, e = fn(params, seqs, pos, tok, first)
        gaps.append(np.asarray(g)[:keep][mask[:keep]].reshape(-1))
        errs.append(np.asarray(e)[:keep])
    return np.concatenate(gaps), np.concatenate(errs)


@dataclasses.dataclass
class Compared:
    """The requests a run compares with the reference, each ``{"prompt",
    "out"}``, and the shape of the reference's blocks: rows of ``length``
    tokens, ``width`` served positions a row, ``block`` rows a call."""
    requests: list[dict]
    length: int
    width: int
    block: int


def compare(cell: Cell, params, compared: Compared, control: bool = False) -> dict:
    """With the reference's weights ``params``: the widest gap of a served
    token below the reference's best (``served_token_gap``), and the
    largest error of a request's first logits (``first_logits_err``). With
    ``control``, the control's readings in the program's place."""
    program_fn, control_fn = reference_fns(cell.reference, cell.sizes)
    t_ref = time.monotonic()
    g, e = read_compared(control_fn if control else program_fn, params, compared, control)
    log(f"reference{' (control)' if control else ''}: {len(g)} served tokens of "
        f"{len(compared.requests)} requests in {time.monotonic() - t_ref:.1f} s")
    if not len(g):
        return {"served_token_gap": float("inf"), "first_logits_err": float("inf")}
    return {"served_token_gap": float(g.max()), "first_logits_err": float(e.max())}


# ----------------------------------------------------------------------
# cold start
# ----------------------------------------------------------------------
def cold_start(cell: Cell) -> Outcome:
    from repro.checkpoint.manager import CheckpointManager
    from repro.serving.engine import ServeEngine

    tr, cfg = cell.traffic, cell.cfg
    burst = tr["burst"]
    like = program_shapes(cfg)
    ck_dir = os.path.join(cell.work_dir, "ckpt")
    shutil.rmtree(ck_dir, ignore_errors=True)
    mgr = CheckpointManager(ck_dir)
    params = make_params(like, cell.seed)
    mgr.save(0, params)
    del params
    manifest = mgr.block_manifest(0)
    stream = RequestStream(tr, cfg.vocab_size, cell.seed)

    # warm-up: one cold start, then a burst of each prompt length
    eng = ServeEngine(cfg, max_batch=burst)
    instrument(eng, BatchClock())
    eng.start(mgr, 0, like, lazy=True)
    wrng = rng_for(cell.seed, 9)
    for t, b in stream.shapes():
        for _ in range(burst):
            eng.submit(wrng.integers(0, cfg.vocab_size, t, dtype=np.int32), b)
        eng.step_batch()
    del eng

    counter = CompileCounter()
    tracer = Tracer(cell.trace, tr.get("trace_seconds", 10),
                    os.path.join(cell.work_dir, "trace"))
    starts, last = [], None
    t0 = time.monotonic()
    tracer.start()
    while True:
        eng = ServeEngine(cfg, max_batch=burst)
        clock = BatchClock()
        instrument(eng, clock)
        for _ in range(burst):
            eng.submit(*stream.next())
        t_a = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.start"):
            eng.start(mgr, 0, like, lazy=True)
        t_b = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step_batch"):
            done = eng.step_batch()
        t_c = time.monotonic()
        starts.append({
            "ttft_s": t_c - t_a, "restore_s": t_b - t_a, "first_batch_s": t_c - t_b,
            "first_fetch_bytes": eng.cold_start_stats["first_fetch_compressed_bytes"],
            "sent": burst,
            "requests": [{"prompt": r.prompt, "out": list(r.out_tokens[:1]),
                          "first_logits": (clock.first_logits, i)}
                         for i, r in enumerate(done)],
        })
        tracer.tick()
        if t_c - t0 >= cell.seconds:
            last = eng
            break
        del eng, done
    t_close = time.monotonic()
    tracer.stop()
    in_window = counter.between(t0, t_close)
    counter.close()
    peak = memory_peak(cell.devices)

    # correct: the restored weights, bit for bit, and every first token
    ref_params = make_params(like, cell.seed)
    restored = last.params
    mism = check.tree_mismatches(restored, ref_params)
    last.params = None
    del restored, last
    reqs = [r for s in starts for r in s["requests"]]
    sent = sum(s["sent"] for s in starts)
    failed = sent - sum(1 for r in reqs if len(r["out"]) == 1)
    compared = Compared([r for r in reqs if r["out"]], max(tr["prompt_lens"]), 1,
                        tr.get("check_block", burst))
    readings = compare(cell, ref_params, compared)
    e2e = {"cold_ttft_s": sum(s["ttft_s"] for s in starts) / len(starts)}
    return Outcome(
        e2e=e2e,
        host={"cold_starts": starts, "window_s": t_close - t0,
              "compressed_bytes": manifest.compressed_size(),
              "raw_bytes": manifest.raw_size,
              "trace_stop_s": tracer.stop_cost},
        checks={"restored_bits_differing": (float(mism), cell.limits["restored_bits_differing"]),
                **{k: (v, cell.limits[k]) for k, v in readings.items()}},
        attempted=sent, failed=failed,
        memory_peak_bytes=peak, window_start=t0, trace_file=tracer.file(),
        compared=compared,
        notes={"compiles_in_window": in_window, "cold_starts": len(starts),
               "compressed_bytes": manifest.compressed_size(),
               # per cold start: prompt length, restore_s, first_batch_s
               "starts": [[len(s["requests"][0]["prompt"]) if s["requests"] else 0,
                           s["restore_s"], s["first_batch_s"]] for s in starts]})
