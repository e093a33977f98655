#!/usr/bin/env python3
"""Record the small device trace that ``bench/tests`` reduce.

On one TPU chip, under the profiler: a jitted matrix product three times
(span ``bench.matmul``), a host span that sleeps 20 ms with the device idle
(``bench.sleep``), and the Pallas flash-attention kernel twice at a small
shape (``bench.flash``). Writes the ``.xplane.pb`` to ``--out`` and a plain-text
summary of its planes, lines and first events beside it. Run from the
root of the checkout::

    python bench/tools/record_trace.py --out runs/small_trace
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def dump(path: str, out) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}", file=out)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}", file=out)
            for e in evs[:12]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={dict(e.stats)}", file=out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace needs a TPU")
    from repro.kernels import ops as kops

    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((2, 4, 256, 128), jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: kops.flash_attention(q, k, v, scale=0.088))
    mm(a, a).block_until_ready()
    flash(q, q, q).block_until_ready()

    tmp = os.path.join(args.out, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.matmul"):
        for _ in range(3):
            mm(a, a).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.sleep"):
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench.flash"):
        for _ in range(2):
            flash(q, q, q).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(args.out, "small_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    with open(os.path.join(args.out, "summary.txt"), "w") as f:
        dump(dst, f)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")


if __name__ == "__main__":
    main()
