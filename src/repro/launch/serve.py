"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--preset one_chip]``.

Builds seeded random weights on the device, saves them as a block-format
checkpoint and drops the device copy. The engine then cold-starts from the
checkpoint via the FaaSNet on-demand path and serves synthetic batched
requests. ``main`` can be called in-process, and so can its two phases,
:func:`cold_start` and :func:`serve_requests`.
"""
from __future__ import annotations

import argparse
import os
from dataclasses import dataclass
from typing import Any

#: ``--preset`` name -> the config module attribute it selects.
PRESETS = {"smoke": "SMOKE", "one_chip": "ONE_CHIP"}


@dataclass
class ColdStart:
    engine: Any  # ServeEngine, started
    ckpt: Any  # CheckpointManager holding the weights at step 0
    like: Any  # the params' tree of jax.ShapeDtypeStruct
    manifest: Any  # BlockManifest of the saved checkpoint


def select_config(arch: str | None, preset: str = "smoke"):
    from repro.configs import ModelConfig, get

    if arch is None:
        return ModelConfig(
            name="serve_default", family="dense", n_layers=4, d_model=192,
            n_heads=6, n_kv_heads=2, d_ff=512, vocab_size=2048,
            attn_impl="full", remat="none",
        )
    mod = get(arch)
    if not hasattr(mod, PRESETS[preset]):
        raise SystemExit(f"{arch} has no {preset!r} config")
    return getattr(mod, PRESETS[preset])


def cold_start(cfg, *, seed: int, ckpt_dir: str, max_batch: int) -> ColdStart:
    """Seeded init on the device, save, drop the device copy, lazy start."""
    import jax

    from repro.checkpoint.manager import CheckpointManager
    from repro.models import model_for
    from repro.serving.engine import ServeEngine

    params = jax.jit(model_for(cfg).init)(jax.random.key(seed))
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(0, params)
    like = jax.eval_shape(lambda: params)
    del params  # the engine restores from the checkpoint alone
    eng = ServeEngine(cfg, max_batch=max_batch)
    eng.start(mgr, 0, like, lazy=True)
    return ColdStart(eng, mgr, like, mgr.block_manifest(0))


def serve_requests(eng, *, n: int, prompt_len: int, max_new_tokens: int,
                   seed: int) -> list:
    """Submit ``n`` random prompts and drain the queue; finished requests."""
    import numpy as np

    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, size=prompt_len),
                   max_new_tokens=max_new_tokens)
    done = []
    while eng.queue:
        done += eng.step_batch()
    return done


def main(argv: list[str] | None = None) -> tuple[ColdStart, list]:
    from repro.launch.runtime import RUNS_DIR, setup_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="an assigned arch id")
    ap.add_argument("--preset", choices=sorted(PRESETS), default="smoke",
                    help="which config of --arch: CPU smoke or one chip's share")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=os.path.join(RUNS_DIR, "serve_ckpt"))
    args = ap.parse_args(argv)

    import numpy as np

    setup_compile_cache()
    cfg = select_config(args.arch, args.preset)
    if cfg.family in ("audio",):
        raise SystemExit("enc-dec serving demo requires frames; use the LM archs")
    cs = cold_start(cfg, seed=0, ckpt_dir=args.ckpt_dir, max_batch=4)
    s = cs.engine.cold_start_stats
    print(f"cold start (lazy): first weights {s['t_first_leaves_s']*1e3:.0f} ms, "
          f"full {s['t_full_s']*1e3:.0f} ms, "
          f"amplification {s['read_amplification']:.2f}x")
    print(f"restore: read {s['read_s']*1e3:.0f} ms, decompress "
          f"{s['decompress_s']*1e3:.0f} ms ({s['blocks_decompressed']} blocks, "
          f"{s['blocks_on_pool']} on the pool, "
          f"{s['blocks_from_cache']} from cache), decode {s['decode_s']*1e3:.0f} ms, "
          f"host-to-device {s['h2d_s']*1e3:.0f} ms ({s['h2d_bytes']/1e6:.1f} MB)")
    done = serve_requests(cs.engine, n=args.requests, prompt_len=args.prompt_len,
                          max_new_tokens=args.max_new_tokens, seed=0)
    print(f"cold start to the first batch's prefill: {s['traces']} traces, "
          f"{s['cache_loads']} executables loaded from the persistent cache")
    lat = [(r.t_done - r.t_submit) * 1e3 for r in done]
    print(f"served {len(done)} requests; latency mean {np.mean(lat):.0f} ms, "
          f"p99 {np.percentile(lat, 99):.0f} ms")
    return cs, done


if __name__ == "__main__":
    main()
