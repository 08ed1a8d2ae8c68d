"""Time a latent-attention layer's prompt-chunk attention alone on the chip: the
ABSORBED form (``q_nope W_UK`` in front of ``dstpu_mla_chunk``'s absorbed body,
``W_UV`` behind it) beside the EXPANDED one (a head's keys and values made of the
cached latents inside the kernel), at A.X-K1's / LongCat's 64 heads and Kimi
Linear's 32, over contexts of 2k / 8k / 32k cached tokens.

    python tools/mla_kernels.py [--heads 64,32 --contexts 2048,8192,32768 --tq 128,512
                                 --group 2,4,8,16 --rows 1 --live 1.0]

Prints one JSON line. ``chunk``: for each (heads, tq, context) microseconds a call
of each form (host clock over ``--reps`` runs of a program of ``CALLS`` calls on
queries of their own: a call alone reads the host's ~0.4 ms dispatch), the
absorbed kernel without its two products, the expanded kernel at each
``--group`` (heads a program), each form's matrix operations as the kernel
issues them (``ops.py``-style arithmetic from the shapes: whole visits of 512
keys, every slot of the row, live or padding: ``--live`` under 1 times a prompt's
tail, whose dead query tiles the absorbed kernel skips) and their share of the v5e's 197 TFLOP/s, the pool bytes a
call reads, and the largest difference between the two forms' outputs (bf16
operands). ``--compile-only`` compiles every kernel of the sweep for a described
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

from deepspeed_tpu.ops.attention import latent_pallas as LP

RANK, DN, DR, DV, BS = 512, 128, 64, 128, 128   # the three configurations' widths
D = RANK + DR
PEAK, HBM = 197e12, 819e9                        # v5e: bf16 FLOP/s, bytes/s
CALLS = 4


def needs(form, rows, tq, nh, context, products=True):
    """(matrix operations, pool bytes) of one call on ``rows`` rows of ``tq``
    queries over ``context`` cached tokens and their own ``tq`` vectors, as the
    kernels walk them (visits of ``wide`` keys, the last of the pool padded)."""
    wide = LP._visit_blocks(tq, BS) * BS
    keys = (-(-context // wide) + tq // wide) * wide
    if form == "absorbed":
        flops = tq * nh * (keys * 2 * (D + RANK) + products * 2 * 2 * RANK * DN)   # + the two products
        reads = tq // LP.chunk_tile(tq, nh)
    else:
        flops = nh * keys * (2 * RANK * (DN + DV) + tq * 2 * (DN + DR + DV))
        reads = nh   # divided by the heads a program below
    return rows * flops, rows * reads * keys * D * 2


def timed(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / CALLS * 1e6, out


def forms(group):
    """name -> f(q, q_rope, wkv_b, pool, tables, q_pos, trash, new, start): one call, q the
    query projection as written ([Rc, tq, nh x (DN + DR)]), q_rope [Rc, tq, nh, DR]."""
    def absorbed(q, qr, w, *rest, kernel_only=False):
        nh = qr.shape[2]
        wr, qn = w.reshape(RANK, nh, DN + DV), LP._nope(q, nh, DR)
        qa = jnp.concatenate([jnp.einsum("rthd,chd->rthc", qn, wr[..., :DN]), qr], axis=-1)
        if kernel_only:   # the products' operands stand in for their results
            qa = jnp.concatenate([qn] * (RANK // DN) + [qr], axis=-1)
        out = LP.latent_chunk_absorbed(qa, *rest, rank=RANK, scale=0.07, impl="kernel")
        return out if kernel_only else jnp.einsum("rthc,chd->rthd", out, wr[..., DN:]).reshape(q.shape[:2] + (-1,))

    out = {"absorbed": absorbed, "absorbed_kernel": functools.partial(absorbed, kernel_only=True)}
    for hg in group:
        out[f"expanded_{hg}"] = functools.partial(
            LP.latent_chunk_expanded, scale=0.07, impl="kernel", heads=hg)
    return out


def chained(fn):
    def run(qs, w, pool, tables, q_pos, new, start):
        return [fn(qn, qr, w, pool, tables, q_pos, pool.shape[0] - 1, new, start) for qn, qr in qs]
    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", default="64,32")
    ap.add_argument("--contexts", default="2048,8192,32768")
    ap.add_argument("--tq", default="128,512")
    ap.add_argument("--group", default="2,4,8,16")
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--live", type=float, default=1.0,
                    help="the share of a row's tq slots that hold a query (a prompt's tail holds fewer)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--compile-only", action="store_true")
    a = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",")]  # noqa: E731
    contexts, group, Rc = ints(a.contexts), ints(a.group), a.rows
    B = -(-(max(contexts) + 512) // BS) + 8
    P = Rc * B + 1
    fns = forms(group)
    programs = {name: chained(fn) for name, fn in fns.items()}
    dt = jnp.bfloat16
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        LP.on_tpu = lambda: True
        one = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        s = lambda shape, d=dt: jax.ShapeDtypeStruct(shape, d, sharding=one)  # noqa: E731
        i32 = jnp.int32
        for nh in ints(a.heads):
            for tq in ints(a.tq):
                for name in fns:
                    qs = [(s((Rc, tq, nh * (DN + DR))), s((Rc, tq, nh, DR)))] * CALLS
                    c = programs[name].lower(qs, s((RANK, nh * (DN + DV))), s((P, D, BS)),
                                          s((Rc, B), i32), s((Rc, tq), i32), s((Rc, tq, D)),
                                          s((Rc,), i32)).compile()
                    print("compiled", nh, tq, name, c.memory_analysis().temp_size_in_bytes, flush=True)
        return
    out = {"device_kind": jax.devices()[0].device_kind, "rows": Rc, "calls_a_program": CALLS, "chunk": []}
    rng = np.random.default_rng(0)
    rnd = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)  # noqa: E731
    pool = rnd(P, D, BS)
    tables = jnp.asarray(rng.permutation(P - 1)[: Rc * B].reshape(Rc, B), jnp.int32)
    for nh in ints(a.heads):
        w = rnd(RANK, nh * (DN + DV)) * RANK ** -0.5
        for tq in ints(a.tq):
            qs = [(rnd(Rc, tq, nh * (DN + DR)), rnd(Rc, tq, nh, DR)) for _ in range(CALLS)]
            new = rnd(Rc, tq, D)
            for ctx in contexts:
                start = jnp.full((Rc,), ctx, jnp.int32)
                live = max(1, int(tq * a.live))
                q_pos = jnp.where(jnp.arange(tq) < live, start[:, None] + jnp.arange(tq, dtype=jnp.int32)[None], -1)
                row = {"heads": nh, "tq": tq, "context": ctx, "live_queries": live}
                outs = {}
                for name in fns:
                    us, o = timed(programs[name], qs, w, pool, tables, q_pos, new, start, reps=a.reps)
                    form = name.split("_")[0]
                    flops, byts = needs(form, Rc, tq, nh, ctx, products=name != "absorbed_kernel")
                    if form == "expanded":
                        byts //= min(int(name.split("_")[1]), nh)
                    row[name] = {"us": us, "tflops": flops / us / 1e6, "peak_pct": 100 * flops / PEAK / (us * 1e-6),
                                 "pool_bytes_us": byts / HBM * 1e6}
                    outs[name] = o[0]
                ref = outs["absorbed"].astype(jnp.float32)   # ("absorbed_kernel" returns latents: not compared)
                row["max_abs_between_forms"] = max(
                    float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref)))
                    for k, o in outs.items() if k.startswith("expanded"))
                row["max_abs_output"] = float(jnp.max(jnp.abs(ref)))
                out["chunk"].append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_kernels.json", "w") as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
