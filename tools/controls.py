"""Controls for a serving cell: does the benchmark's comparison tell a faulty
program from the sound one?

A fault is put into the PROGRAM (or into the weights it is given), a few
requests of the cell's own mix are served through the stack ``dstpu serve``
builds, and the harness's own comparison (``benchmarks.harness.serve.
reference_shortfall``: the SOUND weights through the float32 reference, the
worst shortfall of a served token under the reference's best logit) is printed
beside its limit, ``NEAR_ARGMAX``. One process runs every control of every
seed, one engine at a time; a line a control goes to standard output and to
``chiprun_out/controls.jsonl``.

    python tools/controls.py --cell mimo-v2-flash.serve-agent-long-closed64 \\
        --seeds 3900000601,3900000602 --controls sound,no_sink,all_float8 [--requests 6] [--cap 768]

The cells that have a table of faults are ``CELLS``' keys; a table's docstring
says what each of its controls is (``--controls`` defaults to all of them,
``sound`` first: no fault). On a TPU; 1.5-5 minutes a control. ``--tiny`` (with
``JAX_PLATFORMS=cpu``) drives the same flow on a toy of the model, to try the
tool: never a reading.

"At float8's precision" is float8_e4m3's 3 mantissa bits with the exponent kept
(``jax.lax.reduce_precision(a, 8, 3)``: what a scaled float8 tensor holds). NOT
``a.astype(float8_e4m3fn).astype(bfloat16)``: on the chip that pair left every
value as it was (my chip runs, PR 39: the engine's weights had the sound ones'
checksum and the served tokens were the sound ones', bit for bit), so a control
written with it tells nothing.
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
from typing import Callable, NamedTuple, Optional
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# --tiny: a toy of the mix and of the cell's sizes (the configuration's keys at
# a toy size are the cell's own: ``tiny_hf``)
TINY_MIX = dict(prompt_len={"law": "lognormal", "median": 40, "sigma": 0.5, "min": 24, "max": 96},
                output_len={"law": "uniform", "min": 8, "max": 16})
TINY_ARGS = {"--num-blocks": 64, "--block-size": 8, "--max-context": 128, "--max-blocks-per-seq": 16,
             "--max-concurrent": 4, "--max-queue": 64}


class Fault(NamedTuple):
    """What a control serves under: the engine's configuration (None: the
    sound one), its weights as a function of the sound ones (None: the sound
    ones; ``donates``: the function consumes them, two copies do not fit the
    chip), patches ``(object, attribute, plain -> faulty)`` in force while the
    stack is built and served, and how often the requests are served (the last
    wave is the one compared)."""

    cfg: object = None
    weights: Optional[Callable] = None
    patches: tuple = ()
    waves: int = 1
    donates: bool = False


def float8(a):
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)


def matrices_through_float8(params):
    """Every parameter of two axes or more at float8's precision, in place of
    the tree given (donated)."""
    import jax

    return jax.jit(lambda p: jax.tree.map(lambda a: float8(a) if a.ndim >= 2 else a, p),
                   donate_argnums=0)(params)


def kind_with(name, **rules):
    """``T.RECURRENT[name]`` with some of its rules wrapped, as a patch of the table."""
    def new(table):
        kind = table[name]
        return {**table, name: kind._replace(**{k: wrap(getattr(kind, k)) for k, wrap in rules.items()})}
    return new


def state_through_bf16(name):
    """The state pool and the chunk rule's carried state of kind ``name`` at
    bf16's precision: the precision below what the configuration states."""
    import jax.numpy as jnp

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(a.dtype)

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
    return kind_with(name, decode=decode, chunk=chunk)


# -- the tables: a configuration's faults ---------------------------------------
def mimo_faults(cfg):
    """``no_sink``; ``no_value_scale``; ``bases_swapped``; ``window_256``;
    ``window_not_applied``; ``key_tail_dropped`` (the last 64 dims of every
    key); ``pool_float8`` (every cached key and value at float8's precision);
    ``weights_float8`` (every parameter of two axes or more); ``all_float8``
    (both: the whole computation's inputs in the nearest precision below bf16).
    ``sound`` also reads ``reference_float8`` (``mimo_reference_float8``)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import transformer as T

    def no_sink(params):
        layers = params["layers"]
        sink = jnp.full_like(layers["window"]["sink"], -1e30)
        return dict(params, layers=dict(layers, window=dict(layers["window"], sink=sink)))

    def pools_through_float8(plain):
        def scatter(self, caches, blk, row, side):
            return plain(self, caches, blk, row, tuple(float8(a) for a in side))
        return scatter

    def keys_without_their_tail(plain):
        def qkv(c, lp, a, positions, kind, seq_len=None):
            q, k, v = plain(c, lp, a, positions, kind, seq_len)
            return q, k.at[..., 128:].set(0), v
        return qkv

    pool = ((InferenceEngineV2, "_scatter_kv", pools_through_float8),)
    return {
        "no_sink": Fault(weights=no_sink),
        "no_value_scale": Fault(cfg=dataclasses.replace(cfg, attn_value_scale=1.0)),
        "bases_swapped": Fault(cfg=dataclasses.replace(
            cfg, rope_theta=cfg.window_rope_theta, window_rope_theta=cfg.rope_theta)),
        "window_256": Fault(cfg=dataclasses.replace(cfg, sliding_window=256)),
        "window_not_applied": Fault(patches=((
            InferenceEngineV2, "_layer_windows", lambda plain: lambda self: [0 for _ in plain(self)]),)),
        "key_tail_dropped": Fault(patches=((T, "kind_qkv", keys_without_their_tail),)),
        "pool_float8": Fault(patches=pool),
        "weights_float8": Fault(weights=matrices_through_float8, donates=True),
        "all_float8": Fault(weights=matrices_through_float8, donates=True, patches=pool),
    }


def mimo_reference_float8(hf, mix, params, fresh, entries):
    """Beside ``sound``: the REFERENCE with its weights at float8's precision
    put in the program's place, its argmax at every served position under the
    request's own history, held to the sound reference AT that position.
    Consumes ``params``; ``fresh()`` makes the sound ones anew. Returns (the
    shortfall a request, what the line holds beside it, the sound weights)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import loadgen
    from benchmarks.harness.common import reference_module

    def reference_logits(params):
        """The reference's logits at every served position (as
        ``serve.reference_shortfall`` lays a request out), a request at a time."""
        ref = reference_module(hf)
        max_out = loadgen.quantile_len(mix["output_len"], 1.0 - 1e-9)
        width = -(-(loadgen.quantile_len(mix["prompt_len"], 1.0 - 1e-9) + max_out) // 128) * 128
        for e in entries:
            p, g = e["spec"].prompt, np.asarray(e["req"].generated, np.int32)
            toks = np.zeros(width, np.int32)
            toks[: len(p) + len(g)] = np.concatenate([p, g])
            rows = np.minimum(len(p) - 1 + np.arange(max_out), width - 1)
            yield ref.logits(params, toks, hf, rows=rows)[: len(g)]

    lower = [np.asarray(jnp.argmax(lg, axis=-1)) for lg in reference_logits(matrices_through_float8(params))]
    params = fresh()
    worst = [float(jnp.max(lg.max(-1) - jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)[:, 0]))
             for lg, tok in zip(reference_logits(params), lower)]
    agree = [float(np.mean(tok == np.asarray(e["req"].generated))) for tok, e in zip(lower, entries)]
    return worst, {"agrees_with_served": agree}, params


def longcat_faults(cfg):
    """``identity_dropped`` (the identity pairs add nothing: they are routed as
    experts held elsewhere); ``shortcut_early`` (the expert block's output joins
    behind D_0, one block early); ``plane_swapped`` (sub-block 1 reads sub-block
    0's plane, and writes its own); ``no_kv_scale`` (``mla_scale_kv_lora`` left
    out); ``bias_as_weight`` (a gate is 6 (p + b), not 6 p); ``all_float8``
    (every parameter of two axes or more and every cached vector at float8's
    precision: the whole computation's inputs one precision step down)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.parallel.moe import grouped, moe_mlp

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

    return {
        "identity_dropped": Fault(patches=((grouped, "experts_grouped", identity_as_held_elsewhere),)),
        "shortcut_early": Fault(patches=((InferenceEngineV2, "_shortcut_layer", joins_behind_the_first_mlp),)),
        "plane_swapped": Fault(patches=((InferenceEngineV2, "_kv_source", lower_plane),)),
        "no_kv_scale": Fault(cfg=dataclasses.replace(cfg, latent_kv_scale=1.0)),
        "bias_as_weight": Fault(patches=((grouped, "route", gate_with_the_bias),)),
        "all_float8": Fault(weights=matrices_through_float8, donates=True,
                            patches=((InferenceEngineV2, "_write_back", pool_through_float8),)),
    }


def jamba_faults(cfg):
    """``state_bf16`` (the state pool and the scan's carried state at bf16's
    precision); ``no_norms`` (plain Mamba-1: the RMSNorms on dt, B and C left
    out); ``rotary`` (rotary positions applied in the two attention layers, as
    every other decoder here has them); ``no_conv_bias`` (the conv's bias left
    out); ``no_d`` (the ``D u`` term left out); ``state_lost`` (a decode step's
    update reads a zero state: what a wrong slot would give)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    def without(key):
        def weights(params):
            m = dict(params["layers"]["mamba"])
            m[key] = jnp.zeros_like(m[key])
            return {**params, "layers": {**params["layers"], "mamba": m}}
        return weights

    def decode_from_zero(plain):
        def rule(c, lp, y, extras, live, pool, slots, impl):
            o, _ = plain(c, lp, y, extras, live, jnp.zeros_like(pool), slots, impl)
            return o, pool
        return rule

    def no_small_norms(plain):
        small = {cfg.mamba_dt_rank, cfg.mamba_d_state}

        def norm(x, w, b, kind, eps):
            return x if x.shape[-1] in small else plain(x, w, b, kind, eps)
        return norm

    return {
        "state_bf16": Fault(patches=((T, "RECURRENT", state_through_bf16("mamba")),)),
        "no_norms": Fault(patches=((T, "_norm", no_small_norms),)),
        "rotary": Fault(cfg=dataclasses.replace(cfg, position="rope")),
        "no_conv_bias": Fault(weights=without("mamba_conv_b")),
        "no_d": Fault(weights=without("mamba_d")),
        "state_lost": Fault(patches=((T, "RECURRENT", kind_with("mamba", decode=decode_from_zero)),)),
    }


def kimi_faults(cfg):
    """``head_decay`` (ONE decay a head, its first channel's, in place of a
    decay a key channel: Gated DeltaNet's rule); ``state_bf16`` (the state pool
    and the chunked rule's carried state at bf16's precision); ``rotary``
    (rotary positions applied to the latent layers' shared dims, as every other
    latent model here has them); ``wrong_plane`` (every latent layer reads and
    writes plane 0 of the pool, whatever its ordinal); ``slot_not_zeroed`` (a
    prompt's first chunk starts from whatever its slot holds: the requests are
    served twice through the same slots and the second wave is compared)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import transformer as T

    def head_decay(params):
        """The decay of a head's first channel given to all its channels."""
        H, d = cfg.kda_heads, cfg.kda_head_dim
        kda = dict(params["layers"]["kda"])
        for key in ("kda_dt_bias", "kda_f_b"):
            a = kda[key]
            kda[key] = jnp.broadcast_to(
                a.reshape(a.shape[:-1] + (H, d))[..., :1], a.shape[:-1] + (H, d)).reshape(a.shape)
        return {**params, "layers": {**params["layers"], "kda": kda}}

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

    return {
        "head_decay": Fault(weights=head_decay),
        "state_bf16": Fault(patches=((T, "RECURRENT", state_through_bf16("kda")),)),
        "rotary": Fault(cfg=dataclasses.replace(cfg, position="rope")),
        "wrong_plane": Fault(patches=((InferenceEngineV2, "_ordinal", plane_zero),)),
        "slot_not_zeroed": Fault(patches=((InferenceEngineV2, "_recurrent_layer", never_fresh),), waves=2),
    }


def pair_shares(counters):
    pairs = max(counters.get("moe_pairs_total", 0), 1)
    return {"zero_pair_pct": 100.0 * counters.get("moe_zero_pairs_total", 0) / pairs,
            "held_pair_pct": 100.0 * counters.get("moe_held_pairs_total", 0) / pairs}


def kind_counters(kind):
    return lambda counters: {f"{kind}_chunk_tokens": counters.get(f"{kind}_chunk_tokens_total", 0),
                             f"{kind}_decode_rows": counters.get(f"{kind}_decode_rows_total", 0)}


# a cell: its table of faults, the defaults of --requests / --cap, the
# configuration's keys at a toy size (--tiny), and what its lines hold beside the
# comparison: a checksum of a weight the engine holds (name, path under
# ``params["layers"]``: a fault in the weights shows there), counters of the
# served run, and a reading of its own beside ``sound``
CELLS = {
    "mimo-v2-flash.serve-agent-long-closed64": dict(
        faults=mimo_faults, requests=6, cap=768, checksum=("wq_checksum", ("full", "wq")),
        beside_sound=("reference_float8", mimo_reference_float8),
        tiny_hf=dict(
            vocab_size=128, hidden_size=64, num_hidden_layers=7, num_attention_heads=8,
            num_key_value_heads=2, swa_num_key_value_heads=4, swa_num_attention_heads=8, head_dim=24,
            swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=3, sliding_window=16,
            sliding_window_size=16, max_position_embeddings=512,
            deployment_share={"n_routed_experts": 16, "chips_per_layer": 4, "share_index": 1})),
    "longcat-flash-chat.serve-tool-agent-closed64": dict(
        faults=longcat_faults, requests=6, cap=512, checksum=("wq_b_checksum", ("sub", "wq_b")),
        counters=pair_shares,
        tiny_hf=dict(
            vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=4, ffn_hidden_size=96,
            expert_ffn_hidden_size=32, n_routed_experts=4, zero_expert_num=8, moe_topk=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            max_position_embeddings=512,
            deployment_share={"n_routed_experts": 16, "chips_per_layer": 4, "share_index": 1})),
    "jamba2-3b.serve-doc-reason-closed64": dict(
        faults=jamba_faults, requests=4, cap=256, counters=kind_counters("mamba"),
        tiny_hf=dict(vocab_size=128, hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
                     num_key_value_heads=1, intermediate_size=192, attn_layer_period=4,
                     attn_layer_offset=2, mamba_dt_rank=8, max_position_embeddings=512)),
    "kimi-linear-48b-a3b.serve-doc-xlong-closed64": dict(
        faults=kimi_faults, requests=3, cap=128, counters=kind_counters("kda"),
        tiny_hf=dict(
            vocab_size=128, hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=192, moe_intermediate_size=64, num_experts=4,
            num_experts_per_token=2, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, model_max_length=512,
            linear_attn_config=dict(full_attn_layers=[2, 4], kda_layers=[1, 3], head_dim=32,
                                    num_heads=4, short_conv_kernel_size=4),
            deployment_share=dict(num_experts=8, chips_per_layer=2, share_index=0))),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True, choices=sorted(CELLS))
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default="", help="comma-separated; all of the cell's by default")
    ap.add_argument("--requests", type=int, default=0, help="the cell's own number by default")
    ap.add_argument("--cap", type=int, default=0, help="most tokens an answer gets (the cell's own by default)")
    ap.add_argument("--tiny", action="store_true", help="a toy on the CPU: tries the tool, reads nothing")
    args = ap.parse_args(argv)
    spec = CELLS[args.cell]
    n_requests, cap = args.requests or spec["requests"], args.cap or spec["cap"]

    from benchmarks.harness import loadgen, serve
    from benchmarks.harness.common import Catalog, start_jax

    cat = Catalog()
    cell = cat.cell(args.cell)
    mix, hf = cat.traffic(cell["traffic"]), cat.config(cell["config"])
    if args.tiny:
        mix, hf, cell = {**mix, **TINY_MIX}, {**hf, **spec["tiny_hf"]}, {**cell, "serve_args": TINY_ARGS}
    devices = start_jax(args.tiny, 1)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(devices=devices[:1]))
    cfg = dataclasses.replace(config_from_hf(hf), dtype="bfloat16")
    if args.tiny:
        cfg = dataclasses.replace(cfg, remat=False)
    table = {"sound": Fault(), **spec["faults"](cfg)}
    controls = [c for c in args.controls.split(",") if c] or list(table)
    unknown = sorted(set(controls) - set(table))
    if unknown:
        raise SystemExit(f"unknown controls {unknown}; {args.cell} has: {tuple(table)}")

    def fresh(seed):
        with jax.default_device(devices[0]):
            return jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))

    def served(fault, eng_params, seed):
        """(the last wave's entries, the driver's counters, the checksum of the
        weight the cell names, as the engine holds it)."""
        argv = ["--model", "", "--port", "0"]
        for flag, value in cell["serve_args"].items():
            argv += [flag, str(value)]
        with contextlib.ExitStack() as stack:
            for obj, name, new in fault.patches:
                stack.enter_context(mock.patch.object(obj, name, new(getattr(obj, name))))
            driver, _ = build_serving_stack(serve_parse_args(argv), cfg=fault.cfg or cfg, params=eng_params)
            checksum = {}
            if spec.get("checksum"):
                name, (kind, leaf) = spec["checksum"]
                held = driver.engine.params["layers"][kind][leaf][0, :64, :64].astype(jnp.float32)
                checksum = {name: float(jnp.sum(jnp.abs(held)))}
            driver.start()
            for _ in range(fault.waves):  # the last wave is the one compared
                load = serve.Load(driver)
                for i in range(n_requests):
                    req = loadgen.client_request(seed, i, 0, int(mix["clients"]), mix, int(hf["vocab_size"]))
                    load.submit(loadgen.Spec(req.prompt, min(req.max_new, cap)), time.monotonic())
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
        return load.entries, counters, checksum

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
            fault = table[control]
            eng_params = params
            if fault.donates:
                del params  # the engine's copy takes their place; made anew for the comparison
            if fault.weights is not None:
                eng_params = fault.weights(eng_params)
            entries, counters, checksum = served(fault, eng_params, seed)
            del eng_params
            gc.collect()
            if fault.donates:
                params = fresh(seed)
            lens = [(len(e["spec"].prompt), len(e["req"].generated)) for e in entries]
            served_s = time.monotonic() - t0
            with jax.default_device(devices[0]):
                worst = serve.reference_shortfall(hf, mix, params, entries)
            report(control, seed, worst, {
                "lens": lens, "served_s": served_s, "total_s": time.monotonic() - t0, **checksum,
                **(spec["counters"](counters) if spec.get("counters") else {}),
                "first_tokens": [[int(t) for t in e["req"].generated[:4]] for e in entries]})
            if control == "sound" and spec.get("beside_sound"):
                name, reading = spec["beside_sound"]
                t0 = time.monotonic()
                with jax.default_device(devices[0]):
                    worst, extra, params = reading(hf, mix, params, lambda: fresh(seed), entries)
                report(name, seed, worst, {**extra, "total_s": time.monotonic() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
