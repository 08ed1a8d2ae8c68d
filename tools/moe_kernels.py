"""Time the expert layer's grouped matmul ``dstpu_moe_gmm`` alone on the chip, at
the shapes of the seven expert configurations under ``benchmarks/configs/``,
under each candidate weight block.

    python tools/moe_kernels.py [--configs k-exaone-236b-a23b,a.x-k1 --steps decode,chunk
                                 --products up,down --reps 10]

For each configuration (the experts one chip HOLDS, hidden size, expert width)
x product (``up``: ``[hidden, width]``, what ``w_up`` and ``w_gate`` are;
``down``: ``[width, hidden]``) x step (``decode``: the closed loops' 32 rows;
``chunk``: 32 rows and one 512-token prompt chunk; each token's top-k drawn
evenly over the router's width, the pairs of experts held elsewhere dropped, as
the step drops them) x weight block ``[k, tn]`` (``candidates``: column tiles
from 256 wide to the whole matrix, as far as two fit the kernel's stated VMEM
(``--vmem-mib`` states more: 96 reaches the whole matrix of every configuration);
the one the rule ``grouped._col_tile`` picks is marked ``rule``): microseconds a
call (host clock over ``--reps`` runs of a program of ``CALLS`` calls on rows of
their own: a call alone reads the host's ~0.4 ms dispatch), GB/s of the weights
of the experts that have a row (the bytes ``benchmarks/metrics/
sat_moe_hit_gmm_roofline_pct.py`` counts first), those plus the rows in and out
as a share of the v5e's 819 GB/s, the block's bytes and the call's grid steps,
and the largest difference from the rule's block's output. Prints one JSON line and writes it to
``chiprun_out/moe_kernels.json``. ``--compile-only`` compiles every kernel of
the sweep for a described v5e without a chip (nothing runs, nothing is timed).
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.hf import config_from_hf
from deepspeed_tpu.parallel.moe import grouped

CONFIGS = ("olmoe-1b-7b", "qwen3-next-80b-a3b", "mimo-v2-flash", "k-exaone-236b-a23b", "a.x-k1",
           "longcat-flash-chat", "kimi-linear-48b-a3b")
STEPS = {"decode": 32, "chunk": 32 + 512}   # tokens a step: the closed loops' rows, and a 512-token chunk beside them
HBM = 819e9                                  # v5e: bytes/s
CALLS = 4


def shapes(name):
    """(experts held, router width, top-k, hidden, expert width) of a configuration's file."""
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        cfg = config_from_hf(json.load(f))
    return cfg.n_experts, cfg.router_width, cfg.moe_top_k, cfg.hidden_size, cfg.expert_dim


def group_sizes(rng, tokens, held, width, top_k):
    """Rows an expert of the ``held`` first ids gets when every token takes
    ``top_k`` distinct ids evenly over ``width``."""
    ids = np.argsort(rng.random((tokens, width)), axis=1)[:, :top_k]
    return np.bincount(ids[ids < held], minlength=held).astype(np.int32)


def candidates(k, n):
    """The columns ``tn`` of a bf16 ``[k, n]``'s block worth timing: the rule's
    own, and every power of two from 256 that divides ``n``, up to the whole
    of it, whose two buffers leave an eighth of the kernel's stated VMEM."""
    fits = grouped._VMEM_LIMIT_BYTES * 7 // 16
    return sorted({grouped._col_tile(k, n, 2)} | {
        tn for tn in (256, 512, 1024, 2048, 4096, n) if tn <= n and n % tn == 0 and k * tn * 2 <= fits})


def program(tm, tn):
    def run(rows, w, sizes):
        return [grouped._gmm_pallas(x, w[None], sizes, jnp.int32(0), tm, False, tn=tn) for x in rows]
    return jax.jit(run)


def timed(fn, *args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / CALLS * 1e6, out[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--steps", default="decode,chunk")
    ap.add_argument("--products", default="up,down")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--vmem-mib", type=int, help="state this VMEM limit in place of the kernel's own")
    a = ap.parse_args()
    if a.vmem_mib:
        grouped._VMEM_LIMIT_BYTES = a.vmem_mib << 20
    dt = jnp.bfloat16
    one = None
    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(
            topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    out = {"device_kind": "described v5e" if one else jax.devices()[0].device_kind,
           "calls_a_program": CALLS, "reps": a.reps, "rows": []}
    for name in a.configs.split(","):
        E, width, top_k, h, f = shapes(name)
        for product in a.products.split(","):
            k, n = (h, f) if product == "up" else (f, h)
            rng = np.random.default_rng(0)
            w = None if one else jnp.asarray(rng.standard_normal((E, k, n), np.float32) * k ** -0.5, dt)
            for step in a.steps.split(","):
                tokens = STEPS[step]
                sizes = group_sizes(rng, tokens, E, width, top_k)
                tm = grouped.row_tile(tokens * top_k, 2)
                m = -(-tokens * top_k // tm) * tm
                routed, hit = int(sizes.sum()), int((sizes > 0).sum())
                weights, rows_io = hit * k * n * 2, routed * (k + n) * 2
                rule = grouped._col_tile(k, n, 2)
                row = {"config": name, "product": product, "step": step, "experts": E, "k": k, "n": n,
                       "rows": m, "routed": routed, "experts_hit": hit,
                       "visits": grouped.computed_rows(sizes, tm) // tm, "rule": rule, "blocks": {}}
                if one:
                    s = lambda shape, d=dt: jax.ShapeDtypeStruct(shape, d, sharding=one)  # noqa: E731
                    for tn in candidates(k, n):
                        program(tm, tn).lower([s((m, k))] * CALLS, s((E, k, n)), s((E,), jnp.int32)).compile()
                        print("compiled", name, product, step, (k, tn), flush=True)
                    continue
                rows = [jnp.asarray(rng.standard_normal((m, k), np.float32), dt) for _ in range(CALLS)]
                outs = {}
                for tn in candidates(k, n):
                    us, o = timed(program(tm, tn), rows, w, jnp.asarray(sizes), reps=a.reps)
                    outs[tn] = o[:routed].astype(jnp.float32)
                    row["blocks"][f"{k}x{tn}"] = {
                        "us": us, "hit_weight_gbs": weights / us / 1e3,
                        "hbm_pct": 100 * (weights + rows_io) / HBM / (us * 1e-6),
                        "block_bytes": k * tn * 2, "grid_steps": n // tn * (m // tm + E - 1), "rule": tn == rule}
                for tn, o in outs.items():
                    row["blocks"][f"{k}x{tn}"]["max_abs_from_rule"] = float(jnp.max(jnp.abs(o - outs[rule])))
                out["rows"].append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
    if one:
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_kernels.json", "w") as fh:
        json.dump(out, fh)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
