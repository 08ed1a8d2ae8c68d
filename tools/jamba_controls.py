"""Controls for the cell ``jamba2-3b.serve-doc-reason-closed64``: does the
benchmark's comparison tell a faulty program from the sound one?

A fault is put into the PROGRAM (or into the weights it is given), a few
requests of the cell's own mix are served through the stack ``dstpu serve``
builds, and the harness's own comparison (``benchmarks.harness.serve.
reference_shortfall``: the SOUND weights through the float32 reference, the
worst shortfall of a served token under the reference's best logit) is printed
beside its limit, ``NEAR_ARGMAX``. One process runs every control of every
seed, one engine at a time; a line a control goes to standard output and to
``chiprun_out/controls.jsonl``.

    python tools/jamba_controls.py --seeds 5300000601 \\
        --controls sound,state_bf16,no_norms,rotary [--requests 4] [--cap 256]

Controls: ``sound`` (no fault); ``state_bf16`` (the state pool and the scan's
carried state at bf16's precision: the precision below what the configuration
states); ``no_norms`` (plain Mamba-1: the RMSNorms on dt, B and C left out);
``rotary`` (rotary positions applied in the two attention layers, as every
other decoder here has them); ``no_conv_bias`` (the conv's bias left out);
``no_d`` (the ``D u`` term left out); ``state_lost`` (a decode step's update
reads a zero state: what a wrong slot would give). On a TPU; 2-4 minutes a
control. ``--tiny`` (with ``JAX_PLATFORMS=cpu``) drives the same flow on a toy
of the model, to try the tool: never a reading.
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

CELL = "jamba2-3b.serve-doc-reason-closed64"
# --tiny: the configuration's keys at a toy size, a toy of the mix and of the cell's sizes
TINY_HF = dict(vocab_size=128, hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=1, intermediate_size=192, attn_layer_period=4, attn_layer_offset=2,
               mamba_dt_rank=8, max_position_embeddings=512)
TINY_MIX = dict(prompt_len={"law": "lognormal", "median": 40, "sigma": 0.5, "min": 24, "max": 96},
                output_len={"law": "uniform", "min": 8, "max": 16})
TINY_ARGS = {"--num-blocks": 64, "--block-size": 8, "--max-context": 128, "--max-blocks-per-seq": 16,
             "--max-concurrent": 4, "--max-queue": 64}
CONTROLS = ("sound", "state_bf16", "no_norms", "rotary", "no_conv_bias", "no_d", "state_lost")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--cap", type=int, default=256, help="most tokens an answer gets")
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

    def without(params, key):
        m = dict(params["layers"]["mamba"])
        m[key] = jnp.zeros_like(m[key])
        return {**params, "layers": {**params["layers"], "mamba": m}}

    @contextlib.contextmanager
    def patched(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new(old))
        try:
            yield
        finally:
            setattr(obj, name, old)

    def kind_with(**rules):
        """``T.RECURRENT['mamba']`` with some of its rules wrapped."""
        def new(table):
            kind = table["mamba"]
            return {**table, "mamba": kind._replace(
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

    def decode_from_zero():
        def decode(plain):
            def rule(c, lp, y, extras, live, pool, slots, impl):
                o, _ = plain(c, lp, y, extras, live, jnp.zeros_like(pool), slots, impl)
                return o, pool
            return rule
        return kind_with(decode=decode)

    def no_small_norms(plain):
        small = {cfg.mamba_dt_rank, cfg.mamba_d_state}

        def norm(x, w, b, kind, eps):
            return x if x.shape[-1] in small else plain(x, w, b, kind, eps)
        return norm

    def fault(control, params):
        """(the engine's configuration, its weights, the patches to serve under)."""
        if control == "state_bf16":
            return cfg, params, [(T, "RECURRENT", state_through_bf16())]
        if control == "state_lost":
            return cfg, params, [(T, "RECURRENT", decode_from_zero())]
        if control == "no_norms":
            return cfg, params, [(T, "_norm", no_small_norms)]
        if control == "rotary":
            return dataclasses.replace(cfg, position="rope"), params, []
        if control == "no_conv_bias":
            return cfg, without(params, "mamba_conv_b"), []
        if control == "no_d":
            return cfg, without(params, "mamba_d"), []
        return cfg, params, []

    def served(eng_cfg, eng_params, patches, seed):
        argv = ["--model", "", "--port", "0"]
        for flag, value in cell["serve_args"].items():
            argv += [flag, str(value)]
        with contextlib.ExitStack() as stack:
            for obj, name, new in patches:
                stack.enter_context(patched(obj, name, new))
            driver, _ = build_serving_stack(serve_parse_args(argv), cfg=eng_cfg, params=eng_params)
            driver.start()
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
            entries, counters = served(eng_cfg, eng_params, patches, seed)
            del eng_params
            gc.collect()
            lens = [(len(e["spec"].prompt), len(e["req"].generated)) for e in entries]
            served_s = time.monotonic() - t0
            with jax.default_device(devices[0]):
                worst = serve.reference_shortfall(hf, mix, params, entries)
            report(control, seed, worst, {
                "lens": lens, "served_s": served_s, "total_s": time.monotonic() - t0,
                "mamba_chunk_tokens": counters.get("mamba_chunk_tokens_total", 0),
                "mamba_decode_rows": counters.get("mamba_decode_rows_total", 0),
                "first_tokens": [[int(t) for t in e["req"].generated[:4]] for e in entries]})
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
