"""Controls for the cell ``kimi-linear-48b-a3b.serve-doc-xlong-closed64``: does
the benchmark's comparison tell a faulty program from the sound one?

A fault is put into the PROGRAM (or into the weights it is given), a few
requests of the cell's own mix are served through the stack ``dstpu serve``
builds, and the harness's own comparison (``benchmarks.harness.serve.
reference_shortfall``: the SOUND weights through the float32 reference, the
worst shortfall of a served token under the reference's best logit) is printed
beside its limit, ``NEAR_ARGMAX``. One process runs every control of every
seed, one engine at a time; a line a control goes to standard output and to
``chiprun_out/controls.jsonl``.

    python tools/kimi_linear_controls.py --seeds 5600000601 \\
        --controls sound,head_decay,state_bf16,rotary [--requests 3] [--cap 128]

Controls: ``sound`` (no fault); ``head_decay`` (ONE decay a head, its first
channel's, in place of a decay a key channel: Gated DeltaNet's rule);
``state_bf16`` (the state pool and the chunked rule's carried state at bf16's
precision: the precision below what the configuration states); ``rotary``
(rotary positions applied to the latent layers' shared dims, as every other
latent model here has them); ``wrong_plane`` (every latent layer reads and
writes plane 0 of the pool, whatever its ordinal); ``slot_not_zeroed`` (a
prompt's first chunk starts from whatever its slot holds: the requests are
served twice through the same slots and the second wave is compared). On a
TPU; 3-5 minutes a control. ``--tiny`` (with ``JAX_PLATFORMS=cpu``) drives the
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

CELL = "kimi-linear-48b-a3b.serve-doc-xlong-closed64"
# --tiny: the configuration's keys at a toy size, a toy of the mix and of the cell's sizes
TINY_HF = dict(vocab_size=128, hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=192, moe_intermediate_size=64, num_experts=4,
               num_experts_per_token=2, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, model_max_length=512,
               linear_attn_config=dict(full_attn_layers=[2, 4], kda_layers=[1, 3], head_dim=32,
                                       num_heads=4, short_conv_kernel_size=4),
               deployment_share=dict(num_experts=8, chips_per_layer=2, share_index=0))
TINY_MIX = dict(prompt_len={"law": "lognormal", "median": 40, "sigma": 0.5, "min": 24, "max": 96},
                output_len={"law": "uniform", "min": 8, "max": 16})
TINY_ARGS = {"--num-blocks": 64, "--block-size": 8, "--max-context": 128, "--max-blocks-per-seq": 16,
             "--max-concurrent": 4, "--max-queue": 64}
CONTROLS = ("sound", "head_decay", "state_bf16", "rotary", "wrong_plane", "slot_not_zeroed")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--cap", type=int, default=128, help="most tokens an answer gets")
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
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(devices=devices[:1]))
    cfg = dataclasses.replace(config_from_hf(hf), dtype="bfloat16")
    if args.tiny:
        cfg = dataclasses.replace(cfg, remat=False)

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(a.dtype)

    def fresh(seed):
        with jax.default_device(devices[0]):
            return jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))

    def head_decay(params):
        """The decay of a head's first channel given to all its channels."""
        H, d = cfg.kda_heads, cfg.kda_head_dim
        kda = dict(params["layers"]["kda"])
        for key in ("kda_dt_bias", "kda_f_b"):
            a = kda[key]
            kda[key] = jnp.broadcast_to(
                a.reshape(a.shape[:-1] + (H, d))[..., :1], a.shape[:-1] + (H, d)).reshape(a.shape)
        return {**params, "layers": {**params["layers"], "kda": kda}}

    @contextlib.contextmanager
    def patched(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new(old))
        try:
            yield
        finally:
            setattr(obj, name, old)

    def kind_with(**rules):
        """``T.RECURRENT['kda']`` with some of its rules wrapped."""
        def new(table):
            kind = table["kda"]
            return {**table, "kda": kind._replace(
                **{k: wrap(getattr(kind, k)) for k, wrap in rules.items()})}
        return new

    def state_through_bf16():
        def decode(plain):
            def rule(c, lp, y, extras, live, pool, slots, impl):
                o, pool = plain(c, lp, y, extras, live, bf16(pool), slots, impl)
                return o, bf16(pool)
            return rule

        def chunk(plain):
            def rule(c, lp, y, extras, live, state, impl=None):
                o, state = plain(c, lp, y, extras, live, bf16(state), impl)
                return o, bf16(state)
            return rule
        return kind_with(decode=decode, chunk=chunk)

    def plane_zero(plain):
        def ordinal(self, li):
            return 0 if isinstance(li, int) and self._mc.layer_kinds[li] == "full" else plain(self, li)
        return ordinal

    def never_fresh(plain):
        def layer(self, lp, x, li, rows, carry):
            if rows.get("chk_start") is not None:
                rows = {**rows, "chk_start": jnp.ones_like(rows["chk_start"])}
            return plain(self, lp, x, li, rows, carry)
        return layer

    def fault(control, params):
        """(the engine's configuration, its weights, the patches to serve under)."""
        if control == "state_bf16":
            return cfg, params, [(T, "RECURRENT", state_through_bf16())]
        if control == "head_decay":
            return cfg, head_decay(params), []
        if control == "rotary":
            return dataclasses.replace(cfg, position="rope"), params, []
        if control == "wrong_plane":
            return cfg, params, [(InferenceEngineV2, "_ordinal", plane_zero)]
        if control == "slot_not_zeroed":
            return cfg, params, [(InferenceEngineV2, "_recurrent_layer", never_fresh)]
        return cfg, params, []

    def served(eng_cfg, eng_params, patches, seed, waves=1):
        argv = ["--model", "", "--port", "0"]
        for flag, value in cell["serve_args"].items():
            argv += [flag, str(value)]
        with contextlib.ExitStack() as stack:
            for obj, name, new in patches:
                stack.enter_context(patched(obj, name, new))
            driver, _ = build_serving_stack(serve_parse_args(argv), cfg=eng_cfg, params=eng_params)
            driver.start()
            for _ in range(waves):  # the last wave is the one compared
                load = serve.Load(driver)
                for i in range(args.requests):
                    spec = loadgen.client_request(seed, i, 0, int(mix["clients"]), mix, int(hf["vocab_size"]))
                    load.submit(loadgen.Spec(spec.prompt, min(spec.max_new, args.cap)), time.monotonic())
                for e in load.entries:
                    assert e["req"].wait(timeout=1200) and e["req"].state == "finished", e["req"].state
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

    for seed in (int(s) for s in args.seeds.split(",")):
        params = fresh(seed)
        for control in controls:
            t0 = time.monotonic()
            eng_cfg, eng_params, patches = fault(control, params)
            entries, counters = served(eng_cfg, eng_params, patches, seed,
                                       waves=2 if control == "slot_not_zeroed" else 1)
            del eng_params
            gc.collect()
            lens = [(len(e["spec"].prompt), len(e["req"].generated)) for e in entries]
            served_s = time.monotonic() - t0
            with jax.default_device(devices[0]):
                worst = serve.reference_shortfall(hf, mix, params, entries)
            report(control, seed, worst, {
                "lens": lens, "served_s": served_s, "total_s": time.monotonic() - t0,
                "kda_chunk_tokens": counters.get("kda_chunk_tokens_total", 0),
                "kda_decode_rows": counters.get("kda_decode_rows_total", 0),
                "first_tokens": [[int(t) for t in e["req"].generated[:4]] for e in entries]})
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
