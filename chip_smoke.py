#!/usr/bin/env python3
"""Bring-up smoke of FaaSNet's device plane on a TPU.

Default run, one chip: the deepseek_7b one-chip cut (every published
width, 4 of the 30 layers, Pallas flash attention in prefill) goes through
the normal serving path. Weights made from ``--seed`` on the device are
saved as a block-format checkpoint and dropped from the device;
``ServeEngine`` cold-starts from the checkpoint lazily and serves 8
requests (128-token prompts, 16 new tokens) through ``step_batch``. Then:

* the lazily restored params equal ``CheckpointManager.restore`` bit for bit;
* the served prefill and decode logits match a float32 reference forward;
* the compiled prefill holds the flash kernel as a ``tpu_custom_call``.

``--four-chips`` runs only the FT-shaped weight broadcast: the same params,
flattened to bf16, go from device 0 to the others over a (4, 1)
``("data", "model")`` mesh with the FaaSNet ``pipelined`` schedule and with
``naive``, each compared bit for bit with the root's bytes on every device.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed. Without a TPU the run exits non-zero
before any phase. Run from the root of the checkout::

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.deepseek_7b import ONE_CHIP, ONE_CHIP_CUT  # noqa: E402
from repro.launch.runtime import RUNS_DIR, setup_compile_cache  # noqa: E402
from repro.launch.serve import cold_start, serve_requests  # noqa: E402
from repro.models import model_for  # noqa: E402

CKPT_DIR = os.path.join(RUNS_DIR, "chip_smoke_ckpt")
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 128, 16
# The broadcast payload is cut into this many blocks (pipelined schedule).
N_BLOCKS = 32

# Served logits (bf16 compute) against the float32 reference. bf16 keeps 8
# significant bits (unit roundoff 2**-9). At these widths with 4 layers, a
# CPU run measured 1.5e-2 relative RMS error and 0.08 largest absolute error
# over 1M logits of standard deviation 1; the bounds leave room for the
# chip's accumulation order and for 100x more logits. Lower precision fails
# both: rounding only the activations to float8_e4m3 (4 significant bits)
# gave 0.14 and 0.81 on one layer in the same CPU comparison.
LOGIT_REL_RMS_TOL = 4e-2  # ||served - ref||_2 / ||ref||_2
LOGIT_MAX_ABS_TOL = 0.3  # max |served - ref|, logits have std ~1


class SmokeFailure(SystemExit):
    """A phase's check failed: the run ends non-zero with this message."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(f"chip_smoke FAILED: {what}")
    print(f"  ok: {what}")


def require_tpu(count: int) -> list:
    """Phase 1: the devices JAX found, which must be ``count`` TPU chips."""
    devs = jax.devices()
    d = devs[0]
    print(f"devices: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise SmokeFailure(f"chip_smoke needs a TPU; JAX found {d.platform!r}")
    if len(devs) < count:
        raise SmokeFailure(f"chip_smoke needs {count} TPU chips; found {len(devs)}")
    return devs[:count]


def device_line() -> dict:
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ----------------------------------------------------------------------
# one chip: cold start, serving, checks
# ----------------------------------------------------------------------
def record_logits(eng) -> dict:
    """Wrap the engine's model so every logit step_batch computes is kept,
    together with the tokens it was fed."""
    base = eng.model
    rec = {"base": base, "tokens": [], "prefill": [], "decode": []}

    def prefill(params, batch, cache_len=None):
        logits, cache = base.prefill(params, batch, cache_len=cache_len)
        rec["tokens"].append(batch["tokens"])
        rec["prefill"].append(logits)
        return logits, cache

    def decode_step(params, batch, cache):
        logits, cache = base.decode_step(params, batch, cache)
        rec["tokens"].append(batch["tokens"])
        rec["decode"].append(logits)
        return logits, cache

    eng.model = dataclasses.replace(base, prefill=prefill, decode_step=decode_step)
    return rec


def check_restore_bitwise(cs):
    """The lazily restored params equal a full restore, leaf for leaf, bit
    for bit. The lazy tree is moved to the host first, so the device holds
    one tree at a time; the engine then serves from the full restore, which
    is returned."""
    flat = jax.tree_util.tree_leaves_with_path(cs.engine.params)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    lazy = [np.asarray(x) for _, x in flat]
    del flat
    cs.engine.set_params(None)
    full = cs.ckpt.restore(0, cs.like)
    ref = jax.tree.leaves(full)
    check(len(lazy) == len(ref), f"lazy and full restore hold {len(ref)} leaves")
    for path, a, b in zip(paths, lazy, ref):
        b = np.asarray(b)
        if not (a.shape == b.shape and a.dtype == b.dtype and np.array_equal(
                a.view(f"u{a.itemsize}"), b.view(f"u{b.itemsize}"))):
            check(False, f"leaf {path} differs bit for bit")
    check(True, f"lazy restore equals CheckpointManager.restore bit for bit "
                f"({len(ref)} leaves, {sum(a.nbytes for a in lazy)} bytes)")
    cs.engine.set_params(full)
    return full


def logit_errors(served: jax.Array, ref: jax.Array) -> tuple[float, float]:
    d = served.astype(jnp.float32) - ref
    rel = jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref))
    return float(rel), float(jnp.max(jnp.abs(d)))


def check_logits(cfg, params, rec: dict) -> dict:
    """Served prefill and decode logits against a float32 forward over the
    same tokens, one request batch at a time."""
    ref_model = model_for(dataclasses.replace(
        cfg, attn_impl="full", compute_dtype="float32"))
    ref_fwd = jax.jit(lambda p, t: ref_model.prefill(p, {"tokens": t})[0])
    out = {}
    n_pre = len(rec["prefill"])
    check(n_pre >= 1, f"{n_pre} served batch(es) recorded")
    per_batch = len(rec["decode"]) // n_pre
    for i in range(n_pre):
        toks = rec["tokens"][i * (per_batch + 1):(i + 1) * (per_batch + 1)]
        seq = jnp.concatenate(toks, axis=1)
        with jax.default_matmul_precision("highest"):
            ref = ref_fwd(params, seq)
        pre = rec["prefill"][i]
        t = pre.shape[1]
        dec = jnp.concatenate(rec["decode"][i * per_batch:(i + 1) * per_batch], axis=1)
        check(bool(jnp.isfinite(pre).all()) and bool(jnp.isfinite(dec).all()),
              f"batch {i}: served logits finite, prefill {tuple(pre.shape)}, "
              f"decode {tuple(dec.shape)}")
        for name, served, want in (("prefill", pre, ref[:, :t]),
                                   ("decode", dec, ref[:, t:])):
            rel, mx = logit_errors(served, want)
            out[f"{name}_rel_rms"], out[f"{name}_max_abs"] = rel, mx
            check(rel <= LOGIT_REL_RMS_TOL and mx <= LOGIT_MAX_ABS_TOL,
                  f"batch {i} {name} logits vs float32 reference: relative RMS "
                  f"{rel!r} (<= {LOGIT_REL_RMS_TOL}), max abs {mx!r} "
                  f"(<= {LOGIT_MAX_ABS_TOL})")
    return out


def check_flash_compiled(base_model, params, tokens: jax.Array, cache_len: int) -> None:
    """The prefill, compiled for the chip, runs attention in the kernel."""
    prefill = jax.jit(base_model.prefill, static_argnames="cache_len")
    hlo = prefill.lower(params, {"tokens": tokens}, cache_len=cache_len).compile().as_text()
    check("tpu_custom_call" in hlo,
          "compiled prefill holds the Pallas flash kernel (tpu_custom_call)")


def one_chip(seed: int) -> None:
    cfg = ONE_CHIP
    print(f"config {cfg.name}: d_model={cfg.d_model} n_heads={cfg.n_heads} "
          f"head_dim={cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"n_layers={cfg.n_layers} attn_impl={cfg.attn_impl} "
          f"params={cfg.param_count()}")
    print(f"cut: reduced={ONE_CHIP_CUT['reduced']} source: {ONE_CHIP_CUT['source']}")
    try:
        t0 = time.perf_counter()
        cs = cold_start(cfg, seed=seed, ckpt_dir=CKPT_DIR, max_batch=N_REQUESTS)
        m = cs.manifest
        print(f"checkpoint: codec={m.codec} raw_bytes={m.raw_size} "
              f"compressed_bytes={m.compressed_size()} blocks={m.n_blocks} "
              f"(save + cold start {time.perf_counter() - t0:.1f} s)")
        check(m.codec == "zstd", "checkpoint codec is zstd")
        print(f"cold_start_stats: {cs.engine.cold_start_stats}")
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"peak_bytes_in_use after cold start: {peak}")

        rec = record_logits(cs.engine)
        t0 = time.perf_counter()
        done = serve_requests(cs.engine, n=N_REQUESTS, prompt_len=PROMPT_LEN,
                              max_new_tokens=NEW_TOKENS, seed=seed)
        print(f"served {len(done)} requests in {time.perf_counter() - t0:.1f} s "
              f"(eager, first calls compile)")
        check(len(done) == N_REQUESTS
              and all(len(r.out_tokens) == NEW_TOKENS for r in done),
              f"{N_REQUESTS} requests each got {NEW_TOKENS} tokens")

        params = check_restore_bitwise(cs)
        errs = check_logits(cfg, params, rec)
        print(f"logit errors: {errs}")
        check_flash_compiled(rec["base"], params, rec["tokens"][0],
                             PROMPT_LEN + NEW_TOKENS)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)


# ----------------------------------------------------------------------
# four chips: FT-shaped weight broadcast
# ----------------------------------------------------------------------
def _same_bits(got: np.ndarray, root_bits: np.ndarray) -> bool:
    return got.shape == root_bits.shape and np.array_equal(
        got.view(root_bits.dtype), root_bits)


def broadcast_check(devs: list, root: np.ndarray) -> dict:
    """Broadcast the host payload ``root`` from ``devs[0]`` over a
    (len(devs), 1) mesh with each schedule; every device's shard must equal
    the root's bytes. Returns each schedule's wall time in seconds."""
    from jax.sharding import Mesh

    from repro.distributed.broadcast import broadcast_fn, root_rows

    mesh = Mesh(np.asarray(devs).reshape(len(devs), 1), ("data", "model"))
    root_bits = root.view(f"u{root.dtype.itemsize}")
    check(bool(root_bits.any()), "root payload is not all zero bits")
    times = {}
    for sched in ("pipelined", "naive"):
        fn = broadcast_fn(mesh, schedule=sched, n_blocks=N_BLOCKS)
        rows = root_rows(jax.device_put(root, devs[0]), mesh)
        others = [s for s in rows.addressable_shards if s.device != devs[0]]
        check(all(not bool(jnp.any(s.data)) for s in others),
              f"{sched}: the {len(others)} non-root replicas start from zeros")
        compiled = fn.lower(rows).compile()
        t0 = time.perf_counter()
        out = compiled(rows)
        out.block_until_ready()
        times[sched] = time.perf_counter() - t0
        shards = out.addressable_shards
        check({s.device for s in shards} == set(devs),
              f"{sched}: result has a shard on each of the {len(devs)} devices")
        for s in shards:
            check(_same_bits(np.asarray(s.data), root_bits),
                  f"{sched}: device {s.device.id} holds the root's "
                  f"{root.nbytes} bytes bit for bit")
        del out, shards
        print(f"broadcast {sched}: {times[sched]!r} s wall around "
              f"block_until_ready (information only)")
    return times


def four_chips(seed: int, devs: list) -> None:
    from jax.sharding import SingleDeviceSharding

    from repro.distributed.broadcast import flatten_pytree

    cfg = ONE_CHIP
    model = model_for(cfg)
    flat = jax.jit(lambda k: flatten_pytree(model.init(k), pad_to=N_BLOCKS)[0],
                   out_shardings=SingleDeviceSharding(devs[0]))(jax.random.key(seed))
    root = np.asarray(flat)
    del flat  # each schedule starts from the host copy
    print(f"payload: {cfg.name} params flattened to {root.dtype}, "
          f"{root.nbytes} bytes")
    broadcast_check(devs, root)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the weight broadcast across four chips")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    print(f"compile cache: {setup_compile_cache()}")
    if args.four_chips:
        four_chips(args.seed, devs)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device_line()}))


if __name__ == "__main__":
    main()
