"""dstpu_bench: collective micro-benchmark (reference ``bin/ds_bench`` →
benchmarks/communication sweep: all_reduce/all_gather/all_to_all/
reduce_scatter across message sizes, reporting algbw/busbw).

Runs on whatever mesh is available (real chips, or the virtual CPU mesh via
--cpu_devices N for plumbing checks). Bus bandwidth uses the standard
ring-collective byte multipliers."""

import argparse
import json
import time

import numpy as np


def _bus_factor(op, w):
    # bytes actually moved per rank vs message size (ring algorithms)
    return {
        "all_reduce": 2 * (w - 1) / w,
        "all_gather": (w - 1) / w,
        "reduce_scatter": (w - 1) / w,
        "all_to_all": (w - 1) / w,
    }[op]


def main(argv=None) -> int:
    p = argparse.ArgumentParser("dstpu_bench", description=__doc__)
    p.add_argument("--op", default="all_reduce",
                   choices=["all_reduce", "all_gather", "reduce_scatter", "all_to_all"])
    p.add_argument("--minsize", type=int, default=1 << 20, help="bytes")
    p.add_argument("--maxsize", type=int, default=1 << 28, help="bytes")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--cpu_devices", type=int, default=0,
                   help="force an N-device virtual CPU mesh (plumbing checks)")
    args = p.parse_args(argv)

    import jax

    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    w = len(devs)
    if w < 2:
        print(json.dumps({"error": f"need >=2 devices for collectives, have {w}"}))
        return 1
    mesh = jax.sharding.Mesh(devs, ("x",))

    def collective(x):
        if args.op == "all_reduce":
            return jax.lax.psum(x, "x")
        if args.op == "all_gather":
            return jax.lax.all_gather(x, "x", tiled=True)
        if args.op == "reduce_scatter":
            return jax.lax.psum_scatter(x, "x", tiled=True)
        return jax.lax.all_to_all(x.reshape(w, -1), "x", 0, 0, tiled=False).reshape(-1)

    size = args.minsize
    while size <= args.maxsize:
        n = max(size // 4 // w * w, w * w)  # fp32 elements, divisible shapes
        fn = jax.jit(jax.shard_map(
            collective, mesh=mesh, in_specs=P("x"), out_specs=P("x") if args.op in ("all_reduce",) else P(),
            check_vma=False,
        ))
        # x is the GLOBAL array under shard_map(in_specs=P("x")): each rank's
        # collective message is n/w elements — size the global input so the
        # PER-RANK message matches the sweep size. Build it PRE-SHARDED: an
        # unsharded global array would materialize entirely on device 0 and
        # OOM at large sweep sizes on large meshes.
        n_global = n * w
        # build only the per-device shards (n*4 bytes each): neither host nor
        # any device ever holds the global array
        sharding = jax.sharding.NamedSharding(mesh, P("x"))
        x = jax.make_array_from_callback(
            (n_global,), sharding,
            lambda idx: np.ones((n,), np.float32),
        )
        try:
            out = fn(x)
            jax.block_until_ready(out)
            for _ in range(args.warmup):
                jax.block_until_ready(fn(x))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / args.iters
            nbytes = n * 4  # per-rank message bytes
            algbw = nbytes / dt / 1e9
            print(json.dumps({
                "op": args.op, "size_bytes": nbytes, "time_us": round(dt * 1e6, 1),
                "algbw_GBps": round(algbw, 3),
                "busbw_GBps": round(algbw * _bus_factor(args.op, w), 3),
            }))
        except Exception as e:  # shape/op unsupported at this size
            print(json.dumps({"op": args.op, "size_bytes": size, "error": str(e)[:200]}))
        size *= 4
    return 0
