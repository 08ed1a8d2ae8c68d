"""Rehearsal 3 of the on-chip-measurement guide: compile for a described
``v5e:2x2`` at the real widths, here, with no chip. Run by hand:

    JAX_PLATFORMS=cpu python3 -m benchmarks.tools.compile_check --config qwen3-0.6b --micro-batch 4

Compiles (a) the Pallas kernels the cells use at the configuration's head
geometry (flash forward and backward at the training sequence length, paged
decode at the serving block size) and (b) forward, loss and gradients of one
chip's micro-batch through ``make_loss_fn``, and prints ``memory_analysis()``
so that the micro-batch can be sized: temporaries + 16 bytes a parameter
(+ 2 for bf16 gradients) must stay under nine tenths of the chip. Nothing
runs; a compile that passes is not a chip run.

The program asks ``on_tpu()`` what it runs on and would take its CPU branch
here, so this script answers for it: the steering is here, not an option of
the program.
"""

import argparse
import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--micro-batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--loss-tiles", type=int, default=8)
    ap.add_argument("--skip-kernels", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import deepspeed_tpu.accelerator.device as device

    device.on_tpu = lambda: True
    for name, mod in list(sys.modules.items()):
        if name.startswith("deepspeed_tpu") and getattr(mod, "on_tpu", None) is not None:
            mod.on_tpu = device.on_tpu
    from deepspeed_tpu.models import init_params, make_loss_fn
    from deepspeed_tpu.models.hf import config_from_hf

    for name, mod in list(sys.modules.items()):
        if name.startswith("deepspeed_tpu") and hasattr(mod, "on_tpu"):
            mod.on_tpu = device.on_tpu

    from benchmarks.harness import flops
    from benchmarks.harness.common import Catalog

    hf = Catalog().config(args.config)
    cfg = dataclasses.replace(config_from_hf(hf), loss_tiles=args.loss_tiles)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(devices=[topo.devices[0]]))  # the mesh the constraints name

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)

    nh, nkv, d = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if not args.skip_kernels:
        from deepspeed_tpu.ops.attention.flash_pallas import flash_attention
        from deepspeed_tpu.ops.attention.paged_pallas import paged_attention

        b, s = args.micro_batch, args.seq
        q = jax.ShapeDtypeStruct((b, nh, s, d), jnp.bfloat16, sharding=chip)
        kv = jax.ShapeDtypeStruct((b, nkv, s, d), jnp.bfloat16, sharding=chip)

        def flash(q, k, v, do):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
            return (out,) + tuple(vjp(do))

        c = jax.jit(flash).lower(q, kv, kv, q).compile()
        print(f"flash fwd+bwd b={b} nh={nh} nkv={nkv} s={s} d={d}: compiled; "
              f"temp {c.memory_analysis().temp_size_in_bytes / 1e9:.2f} GB")
        R, B, bs, NB = 32, 32, 128, 256
        sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731

        def paged(q, kc, vc, tb, qpos):
            return paged_attention(q, kc, vc, tb, qpos, NB, impl="kernel")

        c = jax.jit(paged).lower(
            sd((R, nh, d), jnp.bfloat16), sd((NB + 1, bs, nkv, d), jnp.bfloat16),
            sd((NB + 1, bs, nkv, d), jnp.bfloat16), sd((R, B), jnp.int32), sd((R,), jnp.int32),
        ).compile()
        print(f"paged decode R={R} B={B} bs={bs} nkv={nkv} nh={nh} d={d}: compiled")

    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((args.micro_batch, args.seq + 1), jnp.int32,
                                               sharding=chip)}
    loss_fn = make_loss_fn(cfg)
    c = jax.jit(jax.value_and_grad(loss_fn)).lower(on_chip(shapes), batch).compile()
    m = c.memory_analysis()
    n = flops.param_count(hf)
    text = c.as_text()
    print(f"{args.config}: value_and_grad(loss) micro-batch {args.micro_batch} x {args.seq}, "
          f"loss_tiles {args.loss_tiles}: {text.count('tpu_custom_call')} tpu_custom_call sites")
    print(f"  arguments {m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f} GB")
    print(f"  {n / 1e6:.1f} M parameters: training state at 16 B each {16 * n / 1e9:.2f} GB "
          f"(+ {2 * n / 1e9:.2f} GB of bf16 gradients)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
