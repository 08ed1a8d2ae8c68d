"""Time the two state-space kernels alone on the chip, at the widths of a
configuration's Mamba layers, against their ``lax.scan`` oracle.

    python tools/mamba_kernels.py [--d 5120 --n 16 --rows 32 --chunk-rows 2 --tq 512]

Prints one JSON line: microseconds a call of each kernel, what bounds it (bytes
over 819 GB/s; multiply-adds and exponentials counted a state element), and the
largest difference from the oracle. ``--compile-only`` compiles both for a
described v5e without a chip (nothing runs, nothing is timed).
"""

import argparse
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.state_space import mamba_decode, mamba_recurrent, mamba_scan, state_shape


def inputs(key, lead, d, n):
    k = jax.random.split(key, 6)
    u = jax.random.normal(k[0], lead + (d,), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], lead + (d,), jnp.float32) - 3.0)
    B = jax.random.normal(k[2], lead + (n,), jnp.float32)
    C = jax.random.normal(k[3], lead + (n,), jnp.float32)
    z = jax.random.normal(k[4], lead + (d,), jnp.float32)
    return u, dt, B, C, z


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=5120)
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--slots", type=int, default=33)
    ap.add_argument("--chunk-rows", type=int, default=2)
    ap.add_argument("--tq", type=int, default=512)
    ap.add_argument("--compile-only", action="store_true")
    a = ap.parse_args()
    d, n = a.d, a.n
    A = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, d))
    D = jnp.ones((d,), jnp.float32)
    scan = jax.jit(lambda *x: mamba_scan(*x, impl="kernel"))
    dec = jax.jit(lambda *x: mamba_decode(*x, impl="kernel"), donate_argnums=(7,))
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
        r, t, R = a.chunk_rows, a.tq, a.rows
        c1 = scan.lower(s((r, t, d)), s((r, t, d)), s((r, t, n)), s((r, t, n)), s((r, t, d)),
                        s((n, d)), s((d,)), s((r,) + state_shape(d, n))).compile()
        c2 = dec.lower(s((R, d)), s((R, d)), s((R, n)), s((R, n)), s((R, d)), s((n, d)), s((d,)),
                       s((a.slots,) + state_shape(d, n)), s((R,), jnp.int32)).compile()
        print("compiled", c1.memory_analysis().temp_size_in_bytes, c2.memory_analysis().temp_size_in_bytes)
        return
    key = jax.random.PRNGKey(0)
    out = {"device_kind": jax.devices()[0].device_kind, "d": d, "n": n}
    # the chunked scan
    r, t = a.chunk_rows, a.tq
    x = inputs(key, (r, t), d, n)
    S0 = jax.random.normal(jax.random.PRNGKey(1), (r,) + state_shape(d, n), jnp.float32)
    us, (y, S) = timed(scan, *x, A, D, S0)
    y0, S1 = jax.jit(mamba_recurrent)(*x, A, D, S0)
    tokens = r * t
    byts = tokens * (4 * d + 2 * n) * 4 + 2 * r * d * n * 4
    out["scan"] = {
        "rows": r, "tq": t, "us": us, "us_a_token": us / tokens,
        "bytes_us": byts / 819e3, "elements_a_us": tokens * d * n / us,
        "max_abs_y": float(jnp.max(jnp.abs(y - y0))), "max_abs_state": float(jnp.max(jnp.abs(S - S1)))}
    # the one-token update, over a pool
    R = a.rows
    x = inputs(jax.random.PRNGKey(2), (R,), d, n)
    pool = jax.random.normal(jax.random.PRNGKey(3), (a.slots,) + state_shape(d, n), jnp.float32)
    slots = jnp.asarray(np.random.default_rng(0).permutation(a.slots)[:R], jnp.int32)
    yj, pj = jax.jit(lambda *q: mamba_decode(*q, impl="jnp"))(*x, A, D, pool, slots)
    yk, pk = dec(*x, A, D, pool + 0.0, slots)
    out["decode"] = {"rows": R, "max_abs_y": float(jnp.max(jnp.abs(yk - yj))),
                     "max_abs_pool": float(jnp.max(jnp.abs(pk - pj)))}
    p = pool + 0.0
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(50):
        yk, p = dec(*x, A, D, p, slots)
    jax.block_until_ready(p)
    us = (time.perf_counter() - t0) / 50 * 1e6
    byts = R * (2 * d * n * 4 + (4 * d + 2 * n) * 4)
    out["decode"].update(us=us, bytes_us=byts / 819e3, elements_a_us=R * d * n / us)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
