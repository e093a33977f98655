import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks device count on first init.
"""Dry-run of the FaaSNet weight-broadcast schedules on the production mesh.

This is the §Perf cell most representative of the paper's technique: the
checkpoint payload (an arch's bf16 parameters, model-sharded) must reach
every data replica.  For each schedule we lower + compile the ppermute
program, parse collective traffic from the HLO, and model the serialized
link time (rounds are serialized; sends within a round are concurrent on
disjoint links — the schedule generator guarantees single-port validity).

    python -m repro.launch.broadcast_dryrun --arch jamba_v01_52b --mesh both
"""
import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def run_one(arch: str, mesh_kind: str, schedule: str, n_blocks: int,
            outdir: str, compress: bool = False) -> dict:
    from repro.configs import get_config
    from repro.distributed.broadcast import (
        _bcast_body,
        binomial_rounds,
        faasnet_rounds,
    )
    from repro.launch.hlo_analysis import ICI_BW, analyze_hlo
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    import numpy as np

    dp = int(np.prod([mesh.shape[a] for a in axes]))
    tp = mesh.shape["model"]

    cfg = get_config(arch)
    n_items = cfg.param_count()  # one element per parameter
    dtype = jnp.int8 if compress else jnp.bfloat16
    itemsize = 1 if compress else 2
    payload_bytes = n_items * itemsize  # int8 compression halves wire bytes
    # pad so the per-model-shard slice splits evenly into blocks
    per_shard = -(-n_items // tp)
    per_shard = -(-per_shard // n_blocks) * n_blocks
    buf_struct = jax.ShapeDtypeStruct((per_shard * tp,), dtype)

    if schedule == "pipelined":
        rounds_info = faasnet_rounds(dp, n_blocks)
        rounds = len(rounds_info)
        ser_bytes = rounds * (per_shard * itemsize // n_blocks)
    elif schedule == "binomial":
        rounds_info = binomial_rounds(dp)
        rounds = len(rounds_info)
        ser_bytes = rounds * per_shard * itemsize
    elif schedule == "naive":
        rounds_info = None
        rounds = dp - 1
        ser_bytes = rounds * per_shard * itemsize
    elif schedule == "allgather":
        rounds_info = None
        rounds = 1
        ser_bytes = dp * per_shard * itemsize
    else:
        raise ValueError(schedule)

    body = partial(_bcast_body, axes=axes, dp=dp, schedule=schedule,
                   n_blocks=n_blocks, rounds_info=rounds_info)
    fn = jax.shard_map(body, mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                   check_vma=False)
    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(buf_struct)
    compiled = lowered.compile()
    stats = analyze_hlo(compiled.as_text())
    out = {
        "arch": arch,
        "mesh": mesh_kind,
        "schedule": schedule + ("_int8" if compress else ""),
        "dp": dp,
        "n_blocks": n_blocks,
        "payload_gb": payload_bytes / 1e9,
        "per_device_shard_gb": per_shard * itemsize / 1e9,
        "rounds": rounds,
        "hlo_collective_bytes": stats.collective_bytes,
        "hlo_collective_ops": stats.count_by_kind,
        "serialized_bytes_per_link": ser_bytes,
        "modeled_time_s": ser_bytes / ICI_BW,
        "compile_s": round(time.time() - t0, 2),
    }
    os.makedirs(outdir, exist_ok=True)
    name = f"{arch}__{mesh_kind}__{out['schedule']}__b{n_blocks}.json"
    with open(os.path.join(outdir, name), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="jamba_v01_52b")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--schedules", default="naive,allgather,binomial,pipelined")
    ap.add_argument("--n-blocks", type=int, default=32)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--out", default="results/broadcast")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        for sched in args.schedules.split(","):
            r = run_one(args.arch, mk, sched, args.n_blocks, args.out)
            print(
                f"OK {args.arch} {mk:6s} {sched:10s} rounds={r['rounds']:3d} "
                f"coll={r['hlo_collective_bytes']/1e9:8.2f}GB "
                f"modeled={r['modeled_time_s']:7.3f}s "
                f"compile={r['compile_s']}s",
                flush=True,
            )
            if args.compress and sched == "pipelined":
                r = run_one(args.arch, mk, sched, args.n_blocks, args.out,
                            compress=True)
                print(
                    f"OK {args.arch} {mk:6s} {sched}_int8 rounds={r['rounds']:3d} "
                    f"coll={r['hlo_collective_bytes']/1e9:8.2f}GB "
                    f"modeled={r['modeled_time_s']:7.3f}s",
                    flush=True,
                )


if __name__ == "__main__":
    main()
