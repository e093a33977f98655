"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains reduced (smoke) configs end to end with
checkpoint/restart; on a real TPU pod the same entry point takes
``--mesh single|multi`` and jits through the production mesh with the
sharding rules from repro.distributed (the dry-run proves those lower).
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--full-config", action="store_true",
                    help="use the published config (TPU-scale) instead of smoke")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    from repro.configs import get_config, get_smoke
    from repro.launch.runtime import setup_compile_cache
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import run_train

    setup_compile_cache()
    cfg = get_config(args.arch) if args.full_config else get_smoke(args.arch)
    print(f"training {cfg.name}: ~{cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps")
    res = run_train(
        cfg, steps=args.steps, seq_len=args.seq_len, batch=args.batch,
        n_micro=args.n_micro, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, fail_at_step=args.fail_at,
        opt=AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                        total_steps=args.steps),
        log_every=max(1, args.steps // 20),
    )
    for step, loss in sorted(res.losses.items()):
        print(f"step {step:6d}  loss {loss:.4f}")
    if res.resumed_from:
        print(f"(resumed from checkpoint step {res.resumed_from})")


if __name__ == "__main__":
    main()
