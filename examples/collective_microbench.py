"""Collective microbenchmark (reference config #2: the
`ray.util.collective` allreduce/allgather suite whose committed numbers
are bus-bandwidth GB/s over NCCL — BASELINE.md north-star row).

Here the backend is XLA over a device mesh: allreduce lowers to psum
over ICI on real TPU slices (CPU ring on the test backend). Bus
bandwidth uses the standard 2(n-1)/n allreduce traffic model. Run:

    python examples/collective_microbench.py [--size-mb 64] [--iters 10]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.size_mb, args.iters = 4.0, 3

    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps

    from ray_tpu.parallel.ops import shard_map

    # The intra-host/slice data plane: psum/all_gather over the local
    # device mesh — the ICI path the reference reaches via NCCL. (The
    # ray_tpu.util.collective API layers process-group semantics on the
    # same lowering for multi-host actor groups.)
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("world",))
    elems = max(n, int(args.size_mb * 1e6 / 4) // n * n)
    x = jax.device_put(jnp.ones((elems,), jnp.float32),
                       NamedSharding(mesh, Ps("world")))

    allreduce = jax.jit(shard_map(
        functools.partial(jax.lax.psum, axis_name="world"),
        mesh=mesh, in_specs=Ps("world"), out_specs=Ps("world")))
    gather_fn = functools.partial(jax.lax.all_gather, axis_name="world",
                                  tiled=True)
    # all_gather's replicated output needs the replication check off.
    allgather = jax.jit(shard_map(
        gather_fn, mesh=mesh, in_specs=Ps("world"), out_specs=Ps(),
        check_vma=False))

    jax.block_until_ready(allreduce(x))  # compile
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allreduce(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / args.iters
    nbytes = elems * 4
    # NCCL-convention bus bandwidth: algbw * 2(n-1)/n
    algbw = nbytes / dt / 1e9
    busbw = algbw * (2 * (n - 1) / n if n > 1 else 1.0)

    jax.block_until_ready(allgather(x))
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = allgather(x)
    jax.block_until_ready(out)
    ag_dt = (time.perf_counter() - t0) / args.iters
    ag_busbw = (nbytes * (n - 1) / max(n, 1)) / ag_dt / 1e9

    print(json.dumps({
        "workload": "collective_microbench", "devices": n,
        "size_mb": args.size_mb,
        "allreduce_ms": round(dt * 1e3, 3),
        "allreduce_busbw_gbps": round(busbw, 2),
        "allgather_ms": round(ag_dt * 1e3, 3),
        "allgather_busbw_gbps": round(ag_busbw, 2),
    }))


if __name__ == "__main__":
    main()
