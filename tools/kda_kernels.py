"""Time the delta rules alone on the chip, at the widths of Kimi Linear's KDA
layers and of Qwen3-Next's Gated DeltaNet layers, against their ``lax.scan``
oracles, beside a KDA layer's matrix products.

    python tools/kda_kernels.py [--heads 32 --dim 128 --rows 32 --hidden 2304 --gdn-key-heads 16]

Prints one JSON line. ``chunk``: for each rule (``kda``: a decay a key channel,
``gdn``: a decay a head, ``gdn-key-heads`` key heads serving ``heads`` value
heads) on one chunk row and on two, of 128 tokens and of 512 (the shapes
``engine_v2._chunk_bucket`` makes), microseconds a call of the XLA body
(``impl="jnp"``) and of the kernel ``dstpu_kda_chunk`` / ``dstpu_gdn_chunk``
(host clock over 20 runs of a program of 8 calls, each call on inputs of its
own: ``chained``), each one's largest difference from the oracle, and
what bounds the kernel: its products at the peak (float32 at
``Precision.HIGHEST``: six bf16 passes at 197 TFLOP/s) and its bytes at 819
GB/s. ``decode``: the one-token kernel ``dstpu_kda_decode`` over a pool.
``products``: the layer's four wide projections on 1,024 tokens (q | k | v in
one product, the output projection). ``--compile-only`` compiles the chunk
kernels (both rules, the three shapes) and the one-token kernel for a described
v5e without a chip (nothing runs, nothing is timed).
"""

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.linear_attention import (
    gdn_chunked, gdn_recurrent, kda_chunked, kda_decode, kda_recurrent)
from deepspeed_tpu.ops.linear_attention.delta_chunk import _BLOCK, SUB
from deepspeed_tpu.ops.linear_attention.gated_delta import CHUNK, qk_heads

SHAPES = ((1, 128), (1, 512), (2, 512))   # (chunk rows, tq): the split step's programs with chunks
RULES = {"kda": (kda_chunked, kda_recurrent), "gdn": (gdn_chunked, gdn_recurrent)}


def inputs(key, lead, H, d, nk=None, by_channel=True):
    """q, k, v, g, beta as a layer makes them: memories of 10 to 5,000 tokens a
    channel (``by_channel``) or a head; q, k at ``nk`` key heads (default H)."""
    k = jax.random.split(key, 6)
    nk = nk or H
    q, kk = qk_heads(jax.random.normal(k[0], lead + (nk, d)), jax.random.normal(k[1], lead + (nk, d)))
    v = jax.random.normal(k[2], lead + (H, d))
    per = (H, d) if by_channel else (H,)
    tau = jnp.exp(jax.random.uniform(k[3], per, minval=np.log(10.0), maxval=np.log(5000.0)))
    g = -jnp.exp(jax.random.normal(k[4], lead + per)) / tau
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


CALLS = 8  # calls of a chunk rule in one timed program


def chained(fn):
    """``CALLS`` calls of a chunk rule in ONE program, each on inputs of its own
    (nothing to share between calls) from the state the last left, halved: the
    host takes ~0.4 ms to dispatch a program here, more than the kernel runs for,
    so a call timed alone reads the host."""
    def run(xs, S):
        outs = []
        for x in xs:
            o, S = fn(*x, S * 0.5)
            outs.append(o)
        return outs, S

    return jax.jit(run)


def chunk_needs(rule, r, t, nk, nv, d):
    """(floating-point operations of the kernel's products, bytes it moves) for
    ``r`` rows of ``t`` tokens: per head and chunk of C tokens the cumulative
    decay and the sub-blocks' products in front of the diagonal (KDA) or the one
    pair product (GDN); the solve (six products of the packed 16-row blocks, the
    blocks' inverses on the right side and on the rest of ``A``, three steps down
    the block rows); the product with the state, ``qk . v_new`` and the state's
    update."""
    C, B = CHUNK, _BLOCK
    solve = 6 * 2 * B * C * C + 2 * C * C * d + 2 * C ** 3 + (C // B - 1) * 2 * B * C * d
    rest = 2 * (2 * C) * d * d + 2 * C * C * d + 2 * C * d * d
    if rule == "kda":
        pairs = 2 * C * C * d + sum(2 * (2 * SUB) * d * lo for lo in range(SUB, C, SUB))
    else:
        pairs = 2 * (2 * C) * d * C
    flops = r * nv * (t // C) * (pairs + solve + rest)
    decay = nv * d if rule == "kda" else nv
    byts = 4 * r * (t * (2 * nk * d + 2 * nv * d + decay + nv) + 2 * nv * d * d)
    return flops, byts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--gdn-key-heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=2304)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--slots", type=int, default=33)
    ap.add_argument("--compile-only", action="store_true")
    a = ap.parse_args()
    H, d, R = a.heads, a.dim, a.rows
    key_heads = {"kda": H, "gdn": a.gdn_key_heads}
    dec = jax.jit(lambda *x: kda_decode(*x, impl="kernel"), donate_argnums=(5,))
    chunked = {(rule, impl): jax.jit(functools.partial(fns[0], impl=impl))
               for rule, fns in RULES.items() for impl in ("jnp", "kernel")}
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
        for rule, nk in key_heads.items():
            for r, t in SHAPES:
                g = s((r, t, H, d)) if rule == "kda" else s((r, t, H))
                c = chunked[rule, "kernel"].lower(
                    s((r, t, nk, d)), s((r, t, nk, d)), s((r, t, H, d)), g, s((r, t, H)),
                    s((r, H, d, d))).compile()
                print("compiled", rule, r, t, c.memory_analysis().temp_size_in_bytes)
        c2 = dec.lower(*(s((R, H, d)),) * 4, s((R, H)), s((a.slots, H, d, d)), s((R,), jnp.int32)).compile()
        print("compiled decode", c2.memory_analysis().temp_size_in_bytes)
        return
    out = {"device_kind": jax.devices()[0].device_kind, "heads": H, "dim": d, "chunk": []}
    for rule, (_, oracle) in RULES.items():
        nk = key_heads[rule]
        for r, t in SHAPES:
            xs = [inputs(jax.random.PRNGKey(i), (r, t), H, d, nk, rule == "kda") for i in range(CALLS)]
            S0 = jax.random.normal(jax.random.PRNGKey(1), (r, H, d, d), jnp.float32)
            o0, S1 = jax.jit(oracle)(*xs[0], S0)
            flops, byts = chunk_needs(rule, r, t, nk, H, d)
            row = {"rule": rule, "rows": r, "tq": t, "key_heads": nk,
                   "products_us": 6 * flops / 197e6, "bytes_us": byts / 819e3}
            for impl, name in (("jnp", "xla"), ("kernel", "kernel")):
                us, _ = timed(chained(chunked[rule, impl]), xs, S0)
                o, S = chunked[rule, impl](*xs[0], S0)
                row[name] = {"us": us / CALLS, "max_abs_o": float(jnp.max(jnp.abs(o - o0))),
                             "max_abs_state": float(jnp.max(jnp.abs(S - S1)))}
            out["chunk"].append(row)
    # the layer's wide products on two rows' tokens, bf16: q | k | v in one, and the output's
    h, n = a.hidden, 1024
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
