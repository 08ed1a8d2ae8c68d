"""Controls for the cell ``longcat-flash-chat.serve-tool-agent-closed64``: does the
benchmark's comparison tell a faulty program from the sound one?

A fault is put into the PROGRAM (or into the weights it is given), a few
requests of the cell's own mix are served through the stack ``dstpu serve``
builds, and the harness's own comparison (``benchmarks.harness.serve.
reference_shortfall``: the SOUND weights through the float32 reference, the
worst shortfall of a served token under the reference's best logit) is printed
beside its limit, ``NEAR_ARGMAX``. One process runs every control of every
seed, one engine at a time; a line a control goes to standard output and to
``chiprun_out/controls.jsonl``.

    python tools/longcat_controls.py --seeds 4500000601 \
        --controls sound,identity_dropped,all_float8 [--requests 6] [--cap 512]

Controls: ``sound`` (no fault); ``identity_dropped`` (the identity pairs add
nothing: they are routed as experts held elsewhere); ``shortcut_early`` (the
expert block's output joins behind D_0, one block early); ``plane_swapped``
(sub-block 1 reads sub-block 0's plane, and writes its own); ``no_kv_scale``
(``mla_scale_kv_lora`` left out); ``bias_as_weight`` (a gate is 6 (p + b), not
6 p); ``all_float8`` (every parameter of two axes or more and every cached vector
at float8's precision: the whole computation's inputs one precision step down;
``jax.lax.reduce_precision(a, 8, 3)``, as tools/mimo_controls.py says why). On a
TPU; ~1.5-3 minutes a control. ``--tiny`` (with ``JAX_PLATFORMS=cpu``) drives the
same flow on a toy of the model, to try the tool: never a reading.
"""
import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
import weakref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "longcat-flash-chat.serve-tool-agent-closed64"
# --tiny: the configuration's keys at a toy size, a toy of the mix and of the cell's sizes
TINY_HF = dict(
    vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=4, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, n_routed_experts=4, zero_expert_num=8, moe_topk=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    max_position_embeddings=512,
    deployment_share={"n_routed_experts": 16, "chips_per_layer": 4, "share_index": 1})
TINY_MIX = dict(prompt_len={"law": "lognormal", "median": 40, "sigma": 0.5, "min": 24, "max": 96},
                output_len={"law": "uniform", "min": 8, "max": 16})
TINY_ARGS = {"--num-blocks": 64, "--block-size": 8, "--max-context": 128, "--max-blocks-per-seq": 16,
             "--max-concurrent": 4, "--max-queue": 64}
CONTROLS = ("sound", "identity_dropped", "shortcut_early", "plane_swapped", "no_kv_scale",
            "bias_as_weight", "all_float8")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--cap", type=int, default=512, help="most tokens an answer gets")
    ap.add_argument("--tiny", action="store_true", help="a toy on the CPU: tries the tool, reads nothing")
    args = ap.parse_args(argv)
    controls = [c for c in args.controls.split(",") if c]
    unknown = sorted(set(controls) - set(CONTROLS))
    if unknown:
        raise SystemExit(f"unknown controls {unknown}; known: {CONTROLS}")

    from benchmarks.harness import loadgen, serve
    from benchmarks.harness.common import Catalog, start_jax

    cat = Catalog()
    cell = cat.cell(CELL)
    mix, hf = cat.traffic(cell["traffic"]), cat.config(cell["config"])
    if args.tiny:
        mix, hf, cell = {**mix, **TINY_MIX}, {**hf, **TINY_HF}, {**cell, "serve_args": TINY_ARGS}
    devices = start_jax(args.tiny, 1)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.parallel.moe import grouped, moe_mlp
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(devices=devices[:1]))
    cfg = dataclasses.replace(config_from_hf(hf), dtype="bfloat16")
    if args.tiny:
        cfg = dataclasses.replace(cfg, remat=False)

    def float8(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)

    def fresh(seed):
        with jax.default_device(devices[0]):
            return jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))

    def matrices_through_float8(params):
        """Every parameter of two axes or more at float8's precision, in place of the tree given
        (donated: two copies of the weights do not fit the chip)."""
        return jax.jit(lambda p: jax.tree.map(lambda a: float8(a) if a.ndim >= 2 else a, p),
                       donate_argnums=0)(params)

    @contextlib.contextmanager
    def patched(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new(old))
        try:
            yield
        finally:
            setattr(obj, name, old)

    def identity_as_held_elsewhere(plain):
        def experts(config, lp, tokens, logits, live=None, layer=None):
            blind = dataclasses.replace(config, moe_zero_experts=0, moe_experts_total=config.router_width)
            out, aux, counts = plain(blind, lp, tokens, logits, live, layer)
            return out, aux, jnp.concatenate([counts, jnp.zeros(1, counts.dtype)])
        return experts

    def gate_with_the_bias(plain):
        def route(config, logits, live=None, bias=None):
            top_p, top_e, aux, kept = plain(config, logits, live, bias)
            if bias is not None:
                top_p = top_p + config.moe_routed_scale * bias.astype(jnp.float32)[top_e]
            return top_p, top_e, aux, kept
        return route

    def lower_plane(plain):
        def source(self, meta, li, tables):
            return plain(self, meta, li - li % 2, tables)
        return source

    def joins_behind_the_first_mlp(plain):
        del plain

        def layer(self, lp, x, li, meta, carry):
            c, moe = self._mc, None
            for i, sp in enumerate(lp["sub"]):
                plane = 2 * li + i
                attn_out, ckv = self._latent_attention(sp, x, plane, meta)
                carry = dict(carry, k=jax.lax.dynamic_update_index_in_dim(carry["k"], ckv, plane, 0))
                x = x + attn_out
                m = T._norm(x, sp["mlp_norm"], None, c.norm, c.norm_eps)
                x = x + T._mlp_block(c, sp, m)[0]
                if i == 0:
                    shortcut, _, moe = moe_mlp(c, lp, m, live=meta["slot_live"][None], layer=li)
                    x = x + shortcut
            return x, self._record_moe(carry, li, moe)
        return layer

    def pool_through_float8(plain):
        def write_back(self, pools, second, blk, row, side, *a, **kw):
            return plain(self, pools, second, blk, row, dict(side, k=float8(side["k"])), *a, **kw)
        return write_back

    def fault(control):
        """(the engine's configuration, the patches to serve under)."""
        if control == "identity_dropped":
            return cfg, [(grouped, "experts_grouped", identity_as_held_elsewhere)]
        if control == "shortcut_early":
            return cfg, [(InferenceEngineV2, "_shortcut_layer", joins_behind_the_first_mlp)]
        if control == "plane_swapped":
            return cfg, [(InferenceEngineV2, "_kv_source", lower_plane)]
        if control == "no_kv_scale":
            return dataclasses.replace(cfg, latent_kv_scale=1.0), []
        if control == "bias_as_weight":
            return cfg, [(grouped, "route", gate_with_the_bias)]
        if control == "all_float8":
            return cfg, [(InferenceEngineV2, "_write_back", pool_through_float8)]
        return cfg, []

    weights_seen = []

    def served(eng_cfg, eng_params, patches, seed):
        argv = ["--model", "", "--port", "0"]
        for flag, value in cell["serve_args"].items():
            argv += [flag, str(value)]
        with contextlib.ExitStack() as stack:
            for obj, name, new in patches:
                stack.enter_context(patched(obj, name, new))
            driver, _ = build_serving_stack(serve_parse_args(argv), cfg=eng_cfg, params=eng_params)
            # (what the engine holds, to be read beside the line: a fault in the weights shows here)
            held = driver.engine.params["layers"]["sub"]["wq_b"][0, :64, :64].astype(jnp.float32)
            weights_seen.append(float(jnp.sum(jnp.abs(held))))
            driver.start()
            load = serve.Load(driver)
            for i in range(args.requests):
                spec = loadgen.client_request(seed, i, 0, int(mix["clients"]), mix, int(hf["vocab_size"]))
                load.submit(loadgen.Spec(spec.prompt, min(spec.max_new, args.cap)), time.monotonic())
            for e in load.entries:
                assert e["req"].wait(timeout=900) and e["req"].state == "finished", e["req"].state
            counters = dict(driver.metrics.counters)
            driver.shutdown(drain=False, timeout=60)
        engine = weakref.ref(driver.engine)
        load.driver = None
        del driver
        gc.collect()
        if engine() is not None:  # its pools would stand beside the next engine's
            raise SystemExit(f"the engine outlives its driver: held by {gc.get_referrers(engine())[:3]}")
        return load.entries, counters

    def report(control, seed, worst, extra):
        line = {"control": control, "seed": seed, "shortfall": worst, "worst": max(worst),
                "limit": serve.NEAR_ARGMAX, "told": max(worst) > serve.NEAR_ARGMAX, **extra}
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "controls.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        print("CONTROL", json.dumps(line), flush=True)

    params = None
    for seed in (int(s) for s in args.seeds.split(",")):
        del params  # (before the next seed's are made: two sets do not fit the chip)
        params = fresh(seed)
        for control in controls:
            t0 = time.monotonic()
            eng_cfg, patches = fault(control)
            eng_params = params
            if control == "all_float8":
                del params  # the engine's copy takes their place; made anew for the comparison
                eng_params = matrices_through_float8(eng_params)
            entries, counters = served(eng_cfg, eng_params, patches, seed)
            del eng_params
            gc.collect()
            if control == "all_float8":
                params = fresh(seed)
            lens = [(len(e["spec"].prompt), len(e["req"].generated)) for e in entries]
            served_s = time.monotonic() - t0
            with jax.default_device(devices[0]):
                worst = serve.reference_shortfall(hf, mix, params, entries)
            pairs = max(counters.get("moe_pairs_total", 0), 1)
            report(control, seed, worst, {
                "lens": lens, "served_s": served_s, "total_s": time.monotonic() - t0,
                "wq_b_checksum": weights_seen[-1],
                "zero_pair_pct": 100.0 * counters.get("moe_zero_pairs_total", 0) / pairs,
                "held_pair_pct": 100.0 * counters.get("moe_held_pairs_total", 0) / pairs,
                "first_tokens": [[int(t) for t in e["req"].generated[:4]] for e in entries]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
