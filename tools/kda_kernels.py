"""Time Kimi Delta Attention's two rules alone on the chip, at the widths of a
configuration's KDA layers, against their ``lax.scan`` oracle, beside the
layer's matrix products.

    python tools/kda_kernels.py [--heads 32 --dim 128 --rows 32 --chunk-rows 2 --tq 512 --hidden 2304]

Prints one JSON line: microseconds a call of ``kda_chunked`` (plain XLA) on
``chunk-rows`` rows of ``tq`` tokens, of the one-token kernel ``dstpu_kda_decode``
over a pool, of the layer's four wide projections on the same tokens (q | k | v
in one product, the output projection), what bounds the kernel (bytes over 819
GB/s), and the largest difference from the oracle. ``--compile-only`` compiles
both rules for a described v5e without a chip (nothing runs, nothing is timed).
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.linear_attention import kda_chunked, kda_decode, kda_recurrent
from deepspeed_tpu.ops.linear_attention.gated_delta import qk_heads


def inputs(key, lead, H, d):
    """q, k, v, g, beta as a layer makes them: memories of 10 to 5,000 tokens a channel."""
    k = jax.random.split(key, 6)
    q, kk = qk_heads(jax.random.normal(k[0], lead + (H, d)), jax.random.normal(k[1], lead + (H, d)))
    v = jax.random.normal(k[2], lead + (H, d))
    tau = jnp.exp(jax.random.uniform(k[3], (H, d), minval=np.log(10.0), maxval=np.log(5000.0)))
    g = -jnp.exp(jax.random.normal(k[4], lead + (H, d))) / tau
    beta = jax.nn.sigmoid(jax.random.normal(k[5], lead + (H,)))
    return q, kk, v, g, beta


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
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=2304)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--slots", type=int, default=33)
    ap.add_argument("--chunk-rows", type=int, default=2)
    ap.add_argument("--tq", type=int, default=512)
    ap.add_argument("--compile-only", action="store_true")
    a = ap.parse_args()
    H, d, r, t, R = a.heads, a.dim, a.chunk_rows, a.tq, a.rows
    chunk = jax.jit(kda_chunked)
    dec = jax.jit(lambda *x: kda_decode(*x, impl="kernel"), donate_argnums=(5,))
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
        c1 = chunk.lower(*(s((r, t, H, d)),) * 4, s((r, t, H)), s((r, H, d, d))).compile()
        c2 = dec.lower(*(s((R, H, d)),) * 4, s((R, H)), s((a.slots, H, d, d)), s((R,), jnp.int32)).compile()
        print("compiled", c1.memory_analysis().temp_size_in_bytes, c2.memory_analysis().temp_size_in_bytes)
        return
    out = {"device_kind": jax.devices()[0].device_kind, "heads": H, "dim": d}
    x = inputs(jax.random.PRNGKey(0), (r, t), H, d)
    S0 = jax.random.normal(jax.random.PRNGKey(1), (r, H, d, d), jnp.float32)
    us, (o, S) = timed(chunk, *x, S0)
    o0, S1 = jax.jit(kda_recurrent)(*x, S0)
    out["chunked"] = {
        "rows": r, "tq": t, "us": us, "us_a_token": us / (r * t),
        "max_abs_o": float(jnp.max(jnp.abs(o - o0))), "max_abs_state": float(jnp.max(jnp.abs(S - S1)))}
    # the layer's wide products on the same tokens, bf16: q | k | v in one, and the output's
    h, n = a.hidden, r * t
    act = jax.random.normal(jax.random.PRNGKey(2), (n, h), jnp.bfloat16)
    w_in = jax.random.normal(jax.random.PRNGKey(3), (h, 3 * H * d), jnp.bfloat16)
    w_out = jax.random.normal(jax.random.PRNGKey(4), (H * d, h), jnp.bfloat16)
    us_mm, _ = timed(jax.jit(lambda a_, wi, wo: (a_ @ wi)[:, : H * d] @ wo), act, w_in, w_out)
    out["products"] = {"tokens": n, "us": us_mm, "flops_us": 2 * n * h * 4 * H * d / 197e6}
    # the one-token update, over a pool
    x = inputs(jax.random.PRNGKey(5), (R,), H, d)
    pool = jax.random.normal(jax.random.PRNGKey(6), (a.slots, H, d, d), jnp.float32)
    slots = jnp.asarray(np.random.default_rng(0).permutation(a.slots)[:R], jnp.int32)
    oj, pj = jax.jit(lambda *q: kda_decode(*q, impl="jnp"))(*x, pool, slots)
    ok, pk = dec(*x, pool + 0.0, slots)
    out["decode"] = {"rows": R, "max_abs_o": float(jnp.max(jnp.abs(ok - oj))),
                     "max_abs_pool": float(jnp.max(jnp.abs(pk - pj)))}
    p = pool + 0.0
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for _ in range(50):
        ok, p = dec(*x, p, slots)
    jax.block_until_ready(p)
    us = (time.perf_counter() - t0) / 50 * 1e6
    byts = 4 * R * (2 * H * d * d + 5 * H * d + H)
    out["decode"].update(us=us, bytes_us=byts / 819e3, roofline_pct=100 * byts / 819e3 / us)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
