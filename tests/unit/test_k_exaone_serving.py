"""K-EXAONE (``model_type: exaone_moe``) through the paged engine at a tiny
size, seeded weights, on the CPU: window layers whose K/V live in the window
pool (a ring of blocks a tracked sequence) beside global layers in the block
pool, rotary on the window layers only, output-normed blocks, a dense lead
layer and then a share of sigmoid-routed experts.

The oracle is ``benchmarks/reference/k_exaone.py`` (plain float32
``jax.numpy``, a full-sequence forward, no cache): prefill in chunks LONGER
than the window and then decode through both pools, past the point where the
rings wrap, must give the reference's logits."""

import dataclasses
import importlib
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2 import kv_pool
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf import config_from_hf

ref = importlib.import_module("benchmarks.reference.k_exaone")
HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# layer 0 dense, then L L G | L L L G; 16 experts of which share 1 of 4
# (experts 4-7) is held; window 16 over blocks of 8: rings of 3 blocks
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
HF = dict(
    model_type="exaone_moe", vocab_size=128, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=4, num_experts_per_tok=3, num_shared_experts=1,
    norm_topk_prob=True, scoring_func="sigmoid", routed_scaling_factor=2.5, n_group=1,
    topk_group=1, first_k_dense_replace=1, layer_types=KINDS * 3,
    mlp_layer_types=["dense"] + ["sparse"] * 11, sliding_window=16, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    tie_word_embeddings=False, max_position_embeddings=512,
    deployment_share={"num_experts": 16, "chips_per_layer": 4, "share_index": 1},
)
WINDOW, BS = 16, 8
# float32 engine against float32 reference: the same sums in another order
# (worst seen 2e-6 on logits of ~4)
TOL = 2e-5


def _model(hf=HF, dtype="float32", seed=0):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, remat=False)
    return cfg, T.init_params(cfg, jax.random.key(seed))


def _engine(cfg, params, dtype="float32", **extra):
    rc = {
        "dtype": dtype, "prompt_chunk": 40, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": BS, "num_blocks": 64, "max_blocks_per_seq": 32},
        "state_manager": {"max_tracked_sequences": 6, "max_ragged_batch_size": 128,
                          "max_ragged_sequence_count": 4, "max_context": 256},
    }
    for k, v in extra.items():
        rc[k] = {**rc.get(k, {}), **v} if isinstance(v, dict) else v
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict(rc))


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _serve_logits(eng, prompts, n_new):
    """Each prompt's logits at its last prompt token and at ``n_new - 1``
    greedy tokens after it, as the engine's steps return them, and the
    sequences as served."""
    for uid, p in enumerate(prompts):
        eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    toks = {uid: list(p) for uid, p in enumerate(prompts)}
    for _ in range(400):
        for uid, lg in eng.step().items():
            got[uid].append(np.asarray(lg, np.float32))
            if len(got[uid]) < n_new:
                toks[uid].append(int(np.argmax(lg)))
                eng.scheduler.feedback(uid, toks[uid][-1])
            else:
                eng.scheduler.finish(uid)
        if not eng.scheduler.has_work():
            break
    assert not eng.scheduler.has_work()
    return {u: np.stack(g) for u, g in got.items()}, toks


def _gap(params, hf, prompts, got, toks):
    """Worst |engine - reference| over every served logit row."""
    worst = 0.0
    for u, p in enumerate(prompts):
        want = np.asarray(ref.logits(params, np.asarray(toks[u]), hf))
        rows = want[len(p) - 1: len(p) - 1 + len(got[u])]
        worst = max(worst, float(np.abs(got[u] - rows).max()))
    return worst


@pytest.mark.parametrize("lens", [(100,), (5, 70, 100, 33)], ids=["alone", "ragged_batch"])
def test_engine_equals_the_reference_on_logits_float32(lens):
    """Chunks of 40 tokens against a window of 16 and rings of 24 tokens: a
    chunk's own keys ride beside the pool's, the write keeps its last ring;
    then 60 decode steps, past 2 x window + a block, so every ring wraps."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    assert (cfg.window_layers, cfg.kv_layers, eng._win_blocks) == (6, 2, 3)
    prompts = _prompts(lens)
    got, toks = _serve_logits(eng, prompts, 60)
    assert all(len(g) == 60 for g in got.values()) and 60 > 2 * WINDOW + BS
    assert _gap(params, HF, prompts, got, toks) < TOL
    acct = eng.state_manager.kv_block_accounting()
    assert acct["free"] == acct["total"] and acct["window_free"] == acct["window_total"] == 18


def test_engine_equals_the_reference_through_the_interpreted_kernels():
    """The Pallas paged kernels (decode and chunk, interpreted) walk the ring
    tables: the geometry they take (head 128, blocks of 128, window 128: rings
    of 2), a chunk of two blocks and a decode that crosses into a third."""
    hf = {**HF, "head_dim": 128, "sliding_window": 128, "num_hidden_layers": 4}
    cfg, params = _model(hf)
    eng = _engine(
        cfg, params, prompt_chunk=256, max_prompt_chunks=1, paged_attention_impl="kernel",
        kv_cache={"block_size": 128, "num_blocks": 16, "max_blocks_per_seq": 8},
        state_manager={"max_tracked_sequences": 3, "max_ragged_batch_size": 512,
                       "max_ragged_sequence_count": 2, "max_context": 1024})
    assert eng._win_blocks == 2
    prompts = _prompts((300,))
    got, toks = _serve_logits(eng, prompts, 100)  # positions 300..399: block 3 over ring slot 1
    assert _gap(params, hf, prompts, got, toks) < 5 * TOL  # longer sums


@pytest.mark.parametrize("lens,n_new", [((5,), 30), ((5, 70, 100, 33), 12)],
                         ids=["decode_only_steps", "chunk_steps_beside_decode_rows"])
def test_projections_read_in_place_give_the_sliced_forms_logits(monkeypatch, lens, n_new):
    """The four attention projections reach ``stack_dot`` unsliced
    (``_static_layer``); with ``stack_matmul``'s kernel interpreted every
    served logit is what ``x @ stack[index]`` gives (the CPU's default): a
    short prompt and then steps of decode rows alone, and prompts of several
    chunks whose steps carry decode rows beside them."""
    from deepspeed_tpu.ops import stack_matmul as SM

    cfg, params = _model()
    prompts = _prompts(lens)
    sliced, toks = _serve_logits(_engine(cfg, params), prompts, n_new)
    calls, kernel = [], SM.stack_matmul

    def interpreted(x, stack, index, impl=None):
        calls.append((stack.shape, index))
        return kernel(x, stack, index, impl="interpret")

    monkeypatch.setattr(SM, "stack_matmul", interpreted)
    in_place, toks2 = _serve_logits(_engine(cfg, params), prompts, n_new)
    assert toks2 == toks
    for u in sliced:
        np.testing.assert_allclose(in_place[u], sliced[u], atol=TOL, rtol=0)
    # every layer's wq, wk, wv and wo, in every program the run compiled
    assert calls and len(calls) % (4 * cfg.n_layers) == 0
    assert {i for _, i in calls} == set(range(cfg.n_layers))


def _handed_to_the_layers(eng):
    """What ``_drive_layers`` hands each layer: {layer: {key: type name}}."""
    seen = {}

    def spy(lp, x, li, carry, window=None):
        seen[li] = {k: type(v).__name__ for k, v in lp.items()}
        return x, carry

    eng._drive_layers(spy, eng.params, jnp.zeros((1, 1, eng._mc.hidden_size)), {})
    return seen


@pytest.mark.parametrize("case", ["one_device", "a_mesh", "a_stack_of_one", "a_quantized_leaf",
                                  "a_dense_alternating_stack"])
def test_which_projections_stay_in_their_stack(monkeypatch, case):
    """One rule for the common stack and a kind's own (``_static_layer``): a
    plain array of more than one layer on one device goes on as ``Stacked``,
    and nothing else does. A mesh cannot reach the unrolled loop through a
    build today (a window pool and an expert model are both refused at
    ``tp_size`` > 1, as are quantized weights: the last case holds the dense
    alternating stack to that), so the guards are driven here."""
    from deepspeed_tpu.inference.quantization.quantize import QuantizedWeight, quantize_inference_params
    from deepspeed_tpu.ops.stack_matmul import Stacked

    cfg, params = _model()
    eng = _engine(cfg, params)
    four = ("wq", "wk", "wv", "wo")
    if case == "one_device":
        seen = _handed_to_the_layers(eng)
        assert sorted(seen) == list(range(cfg.n_layers))
        for li, types in seen.items():
            stacked = {k for k, t in types.items() if t == "Stacked"}
            assert stacked == set(four), (li, types)
        assert "w_up" in seen[0] and "router" in seen[1]     # the lead layer's MLP, an expert layer's block
    elif case == "a_mesh":
        monkeypatch.setattr(eng, "_mesh", object())
        assert not any(t == "Stacked" for types in _handed_to_the_layers(eng).values() for t in types.values())
    elif case == "a_stack_of_one":
        one = {k: params["layers"][k][:1] for k in four}
        lp = eng._static_layer(one, 0)
        assert all(isinstance(lp[k], jax.Array) and lp[k].shape == one[k].shape[1:] for k in four)
        np.testing.assert_array_equal(lp["wq"], params["layers"]["wq"][0])
        many = eng._static_layer({k: params["layers"][k][:2] for k in four}, 1)
        assert all(isinstance(many[k], Stacked) and many[k].index == 1 for k in four)
    elif case == "a_dense_alternating_stack":
        # gemma-2's shape: no experts, no lead layer, window and global layers in turn
        from deepspeed_tpu.models import get_config

        dense = get_config("tiny", n_layers=4, dtype="float32", max_seq_len=512,
                           sliding_window=16, attn_layer_pattern=(1, 0, 1, 0))
        weights = T.init_params(dense, jax.random.key(0))
        seen = _handed_to_the_layers(_engine(dense, weights))
        assert sorted(seen) == [0, 1, 2, 3]
        assert all({k for k, t in types.items() if t == "Stacked"} == set(four) for types in seen.values())
        with pytest.raises(NotImplementedError, match="window layers keep their K/V"):
            _engine(dense, weights, tp_size=2)
    else:
        q = quantize_inference_params({k: params["layers"][k] for k in four}, bits=8, group_size=32)
        assert all(isinstance(q[k], QuantizedWeight) for k in four)
        lp = eng._static_layer(q, 3)
        assert all(isinstance(lp[k], QuantizedWeight) for k in four)
        np.testing.assert_array_equal(
            T._dequant_tree(lp, jnp.float32)["wq"], T._dequant_tree(q, jnp.float32)["wq"][3])


def test_generate_equals_the_driven_core_through_both_pools():
    """``generate()`` is the served step: the same prompts through the serving
    driver give the same tokens, and over 50 tokens (a ring wraps) each is the
    reference's best."""
    from tests.unit.simple_model import served_tokens

    cfg, params = _model()
    prompts = _prompts((5, 70, 100))
    outs = _engine(cfg, params).generate(prompts, max_new_tokens=50)
    served = served_tokens(_engine(cfg, params), prompts, 50)
    for p, out, got in zip(prompts, outs, served):
        assert [int(t) for t in out[len(p):]] == got
        want = np.asarray(ref.logits(params, out, HF))
        served = out[len(p):]
        best = want[len(p) - 1: -1]
        # greedy on float32 logits: the served token is the reference's best
        # (or within the rounding of a tie)
        chosen = np.take_along_axis(best, served[:, None], axis=-1)[:, 0]
        assert float((best.max(-1) - chosen).max()) < TOL


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute. Against the float32 reference a model this
    small says little (a rounding that turns one of 3 experts of 16 at a width
    of 64 moves logits by more than a fault would: at the published widths the
    chip's comparison holds the served tokens to the reference). So every
    expert is chosen here (top 4 of the 4 held, no share: no decision to
    turn), and the bf16 engine is held to the no-cache ``forward()`` in bf16
    on the same weights: chunks, both pools and paged attention round at other
    places than one dense pass does, and nothing else may differ. Measured
    0.03 on logits of scale 1 (the oracle's own logits are bf16), limit 0.08."""
    hf = {**HF, "num_experts_per_tok": 4, "deployment_share": None}
    cfg, params = _model(hf, dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._wk_cache.dtype == jnp.bfloat16
    prompts = _prompts((70, 33))
    got, toks = _serve_logits(eng, prompts, 40)
    for u, p in enumerate(prompts):
        want = np.asarray(T.forward(params, jnp.asarray(toks[u])[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(got[u], want[len(p) - 1:], atol=0.08, rtol=0)


# -- controls: each must FAIL the comparison ---------------------------------
def _control_gap(monkeypatch, patch):
    cfg, params = _model()
    patch(monkeypatch)
    ref.layer.clear_cache()
    try:
        prompts = _prompts((100,))
        got, toks = _serve_logits(_engine(cfg, params), prompts, 60)
        return _gap(params, HF, prompts, got, toks)
    finally:
        monkeypatch.undo()
        ref.layer.clear_cache()


def test_control_window_blocks_not_wrapped_fails(monkeypatch):
    """Ring tables that stop at the ring's last block instead of wrapping: a
    row past its first ring reads keys that were never written there."""
    def patch(mp):
        def no_wrap(self, slots):
            B, wb = self.config.kv_cache.max_blocks_per_seq, self._win_blocks
            return slots[:, None] * wb + jnp.minimum(jnp.arange(B, dtype=jnp.int32), wb - 1)[None]
        mp.setattr(InferenceEngineV2, "_ring_tables", no_wrap)
    assert _control_gap(monkeypatch, patch) > 1000 * TOL


def test_control_rotary_on_a_full_layer_fails(monkeypatch):
    def patch(mp):
        plain = ref.attention
        mp.setattr(ref, "attention", lambda x, lp, **kw: plain(x, lp, **{**kw, "rotary": True}))
    assert _control_gap(monkeypatch, patch) > 1000 * TOL


def test_control_selection_bias_used_as_a_weight_fails(monkeypatch):
    def patch(mp):
        def biased(x, router, bias, top_k, scale):
            scores = jax.nn.sigmoid(x @ router) + bias
            top_s, top_e = jax.lax.top_k(scores, top_k)
            top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale
            return jnp.sum(jax.nn.one_hot(top_e, scores.shape[-1]) * top_s[..., None], axis=1)
        mp.setattr(ref, "routing_weights", biased)
    assert _control_gap(monkeypatch, patch) > 100 * TOL


# -- the expert block ---------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The SHARE test: each of the 4 chips that share a layer routes over all
    16 experts and computes its own 4; the four partial results, with what
    every chip computes alike (the ungated shared expert) counted once, add up
    to the reference's uncut layer over all 16."""
    from deepspeed_tpu.parallel.moe import moe_mlp
    from deepspeed_tpu.parallel.moe.sharded_moe import _moe_tail

    uncut_hf = {**HF, "num_experts": 16, "deployment_share": None, "num_hidden_layers": 4}
    cfg_all, params = _model(uncut_hf)
    lp_all = T.take_layer(params["layers"], cfg_all, 1, lambda a, i: a[i])  # the first expert layer
    x = jax.random.normal(jax.random.key(7), (1, 24, 64))
    with jax.default_matmul_precision("highest"):
        parts = []
        for share in range(4):
            cfg = dataclasses.replace(cfg_all, n_experts=4, moe_experts_total=16, moe_expert_shard=share)
            lp = {k: (v[4 * share: 4 * share + 4] if k in ("w_up", "w_gate", "w_down") else v)
                  for k, v in lp_all.items()}
            out, _, counts = moe_mlp(cfg, lp, x)
            parts.append((out[0], counts))
        shared = _moe_tail(cfg_all, lp_all, x[0], jnp.zeros_like(x[0]))
        total = sum(o - shared for o, _ in parts) + shared
        want = ref.sparse_mlp(x[0], params["layers"]["sparse"], 0, top_k=3, scale=2.5, first=0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    assert sum(int(c.sum()) for _, c in parts) == 24 * 3  # every pair is some share's


def test_router_bias_moves_the_choice_and_not_the_weight():
    from deepspeed_tpu.parallel.moe.grouped import route

    cfg, _ = _model({**HF, "num_experts": 16, "deployment_share": None})
    logits = jax.random.normal(jax.random.key(3), (32, 16))
    w0, e0, *_ = route(cfg, logits, bias=jnp.zeros(16))
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.5, rtol=1e-6)  # renormalised, x 2.5
    scores = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(
        np.asarray(w0), 2.5 * np.take_along_axis(scores, np.asarray(e0), -1)
        / np.take_along_axis(scores, np.asarray(e0), -1).sum(-1, keepdims=True), rtol=1e-6)
    # a bias that lifts expert 5 over everything: chosen by every token, and
    # weighted by its sigmoid alone
    bias = jnp.zeros(16).at[5].set(10.0)
    w1, e1, *_ = route(cfg, logits, bias=bias)
    assert bool(jnp.all(jnp.any(e1 == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(e0 == 5, axis=-1)))
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.5, rtol=1e-6)
    picked = np.take_along_axis(scores, np.asarray(e1), -1)
    np.testing.assert_allclose(np.asarray(w1), 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)


# -- config_from_hf -----------------------------------------------------------
def test_config_from_hf_on_the_published_keys():
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "k-exaone-236b-a23b.json")))
    cfg = config_from_hf({**row, **row["published"], "deployment_share": None})
    assert (cfg.n_layers, cfg.hidden_size, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (48, 6144, 64, 8, 128)
    assert cfg.attn_layer_pattern == (1, 1, 1, 0) * 12 and cfg.sliding_window == 128
    assert (cfg.window_layers, cfg.kv_layers) == (36, 12)
    assert cfg.rope_window_only and cfg.rope_theta == 1e6 and cfg.norm_scheme == "out" and cfg.qk_norm
    assert (cfg.moe_dense_lead, cfg.ffn_dim, cfg.expert_dim, cfg.moe_shared_expert_dim) == (1, 18432, 2048, 2048)
    assert (cfg.n_experts, cfg.router_width, cfg.moe_top_k) == (128, 128, 8)
    assert (cfg.moe_score, cfg.moe_routed_scale, cfg.moe_shared_gated, cfg.moe_drop_tokens) == (
        "sigmoid", 2.5, False, False)
    assert (cfg.vocab_size, cfg.tie_embeddings) == (153600, False)
    cut = config_from_hf(row)  # the benchmark's cut: a stage's layers, a share of the experts
    assert (cut.n_layers, cut.attn_layer_pattern) == (8, (1, 1, 1, 0, 1, 1, 1, 0))
    assert (cut.n_experts, cut.router_width, cut.moe_expert_shard, cut.vocab_size) == (16, 128, 0, 19200)
    assert T.cache_kinds(cut) == ("window",) * 3 + ("full",) + ("window",) * 3 + ("full",)
    assert T.cache_ordinals(cut) == (0, 1, 2, 0, 3, 4, 5, 1)


@pytest.mark.parametrize("change,match", [
    ({"n_group": 2, "topk_group": 3}, "n_group"),   # (groups themselves are served: PR 37)
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"mlp_layer_types": ["sparse"] * 8}, "mlp_layer_types"),
    ({"mlp_layer_types": ["dense", "sparse", "dense"] + ["sparse"] * 5}, "mlp_layer_types"),
    ({"layer_types": ["sliding_attention"] * 4}, "layer_types"),
    ({"layer_types": ["linear_attention"] * 8}, "layer_types"),
    ({"layer_types": ["full_attention"] * 8}, "no sliding_attention layer"),
    ({"sliding_window": 0}, "without a sliding_window"),
    ({"deployment_share": {"num_experts": 16, "chips_per_layer": 3}}, "not one chip's share"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_type"),
], ids=["groups", "softmax", "no_lead", "dense_behind", "short_list", "unknown_kind",
        "no_window_layer", "no_window", "share", "rope_scaling"])
def test_config_from_hf_refuses_what_it_cannot_compute(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**HF, **change})


def test_v1_decode_step_refuses_the_architecture():
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="v2 paged engine"):
        T.decode_step(params, jnp.zeros((1, 1), jnp.int32), cfg, None, jnp.zeros((1, 1), jnp.int32))


def test_load_hf_model_reads_a_checkpoint_with_the_assumed_names(tmp_path):
    """A checkpoint written under the names ``_exaone_moe_layer`` reads
    (Exaone4's attention and norms, DeepseekV3's expert block, all 16 experts
    and an ``mtp.`` layer that the load drops) comes back as the seeded tree:
    this chip's share of the experts, the router whole."""
    import torch

    from deepspeed_tpu.models.hf import load_hf_model

    cfg, params = _model()
    _, whole = _model({**HF, "num_experts": 16, "deployment_share": None})
    L, lw = params["layers"], whole["layers"]
    state = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"].T, "mtp.layers.0.input_proj.weight": np.zeros((2, 2))}
    mlp = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    for i in range(8):
        p = f"model.layers.{i}"
        state[f"{p}.post_attention_layernorm.weight"] = L["attn_norm"][i]
        state[f"{p}.post_feedforward_layernorm.weight"] = L["mlp_norm"][i]
        for n in "qkvo":
            state[f"{p}.self_attn.{n}_proj.weight"] = L[f"w{n}"][i].T
        state[f"{p}.self_attn.q_norm.weight"] = L["q_norm"][i]
        state[f"{p}.self_attn.k_norm.weight"] = L["k_norm"][i]
        if i == 0:
            for ours, theirs in mlp:
                state[f"{p}.mlp.{theirs}.weight"] = L["lead"][ours][0].T
            continue
        S = L["sparse"]
        state[f"{p}.mlp.gate.weight"] = S["router"][i - 1].T
        state[f"{p}.mlp.gate.e_score_correction_bias"] = S["router_bias"][i - 1]
        for ours, theirs in mlp:
            state[f"{p}.mlp.shared_experts.{theirs}.weight"] = S[f"shared_{ours[2:]}"][i - 1].T
            for e in range(16):  # this chip's experts are 4-7; the others from the uncut draw
                w = S[ours][i - 1, e - 4] if 4 <= e < 8 else lw["sparse"][ours][i - 1, e]
                state[f"{p}.mlp.experts.{e}.{theirs}.weight"] = w.T
    torch.save({k: torch.tensor(np.asarray(v, np.float32)) for k, v in state.items()},
               tmp_path / "pytorch_model.bin")
    json.dump(HF, open(tmp_path / "config.json", "w"))
    got_cfg, got = load_hf_model(str(tmp_path), dtype="float32")
    assert got_cfg == dataclasses.replace(cfg, remat=True)
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert set(flat_got) == {k for k, _ in flat_want}
    for k, v in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[k]), np.asarray(v), err_msg=str(k))


# -- the cache, by kind ---------------------------------------------------------
def test_a_window_layers_blocks_are_a_ring_at_any_context():
    """A tracked sequence holds ``wb`` ring blocks a window layer whatever its
    context, and global blocks by its length; a finished sequence returns
    both; the step's counters say the same."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    mgr = eng.state_manager
    seen = []
    for uid, p in enumerate(_prompts((100, 9))):
        eng.scheduler.submit(uid, p)
    for _ in range(8):
        for uid, lg in eng.step().items():
            eng.scheduler.feedback(uid, int(np.argmax(lg)))
        acct = mgr.kv_block_accounting()
        seen.append((acct["live"], acct["window_live"]))
        st = eng.last_step
        assert st.kv_window_blocks == acct["window_live"] == 2 * 3
        assert st.kv_global_blocks == acct["live"] == sum(
            len(mgr.get_sequence(u).block_table) for u in (0, 1))
        assert st.kv_context_tokens == sum(mgr.get_sequence(u).seen_tokens for u in (0, 1))
    assert seen[-1][0] > seen[0][0] and {w for _, w in seen} == {6}  # global grows, the rings do not
    # decode rows at p = 104 and 13 (after 4 + 1 decode steps): a window layer's
    # walk covers keys p-15 .. p-1
    st = eng.last_step
    pos = [mgr.get_sequence(u).seen_tokens - 1 for u in (0, 1)]
    want = sum((p - 1) // BS - max(p - WINDOW + 1, 0) // BS + 1 for p in pos)
    assert st.paged_window_live_blocks == want and st.paged_live_blocks == sum(-(-p // BS) for p in pos)
    for uid in (0, 1):
        eng.scheduler.finish(uid)
    assert mgr.kv_block_accounting() == {
        "total": 64, "free": 64, "live": 0, "cached_only": 0,
        "window_total": 18, "window_free": 18, "window_live": 0}
    info = eng.kv_pool_info()
    assert (info["window_slots"], info["window_blocks_per_slot"], info["window_slots_in_use"]) == (7, 3, 0)
    assert info["window_bytes_per_slot"] == 6 * 3 * (2 * BS * 2 * 16 * 2)


def test_the_write_waits_for_the_stream_and_changes_nothing_of_it():
    """``_write_back`` orders an unrolled stack's pool write behind the stream
    its last layer left (``x``), through a predicate no stream makes true:
    with a stream that is not a number every token's K/V still lands in its
    block and its ring, as the plain scatter of each pool puts it."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    pools, second = eng._split_pools(eng._pools())
    n = 5
    rng = np.random.default_rng(3)
    # side buffers by pool: "k" / "v" the block pool's layers, "wk" / "wv" the window pool's
    side = {k: jnp.asarray(rng.normal(size=(layers, n, 2, 16)), jnp.float32)
            for k, layers in (("k", cfg.kv_layers), ("v", cfg.kv_layers),
                              ("wk", cfg.window_layers), ("wv", cfg.window_layers))}
    blk, row = jnp.arange(n, dtype=jnp.int32) + 3, jnp.arange(n, dtype=jnp.int32)
    wblk = jnp.arange(n, dtype=jnp.int32) % 3
    want = (eng._scatter_kv(pools, blk, row, (side["k"], side["v"]))
            + eng._scatter_kv(second, wblk, row, (side["wk"], side["wv"])))
    for x000 in (jnp.nan, jnp.inf, 0.0, -1.5):
        x = jnp.zeros((1, n, cfg.hidden_size), jnp.float32).at[0, 0, 0].set(x000)
        got = eng._write_back(pools, second, blk, row, side, wblk, x)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(want[0][0, 3, 0]).sum()) > 0 and float(jnp.abs(want[2][0, 0, 0]).sum()) > 0


def test_admission_stalls_on_global_blocks_only():
    """With the block pool short, a long prompt waits while a short one is
    served; no sequence ever waits for a ring."""
    cfg, params = _model()
    eng = _engine(cfg, params, kv_cache={"num_blocks": 8})
    long, short = _prompts((60, 6))
    eng.scheduler.submit(0, long)   # 8 blocks: the whole pool
    out = {}
    for _ in range(2):
        out.update(eng.step())
    eng.scheduler.feedback(0, int(np.argmax(out[0])))
    eng.scheduler.submit(1, short)  # a ring is free, a global block is not
    for _ in range(3):
        assert set(eng.step()) <= {0}
    assert eng.state_manager.get_sequence(1).seen_tokens == 0
    assert eng.state_manager.state_slot_accounting()["live"] == 2  # it holds its ring already
    eng.scheduler.finish(0)
    assert 1 in eng.step()
    with pytest.raises(ValueError, match="KV blocks"):
        eng.scheduler.submit(2, np.zeros(70, np.int32))  # more global blocks than exist


def test_pool_bytes_pay_for_the_window_rings_first():
    """``--kv-pool-bytes`` buys the window layers' rings (one a tracked
    sequence and a spare) first and blocks of the GLOBAL layers with the rest:
    the published widths' arithmetic of the benchmark's cell; by kind, the
    bytes sum to the budget less under one block."""
    hf = json.load(open(os.path.join(HERE, "benchmarks", "configs", "k-exaone-236b-a23b.json")))
    cfg = config_from_hf(hf)
    assert kv_pool.window_blocks(128, 128) == 2 and kv_pool.window_blocks(16, 8) == 3
    assert kv_pool.window_blocks(129, 128) == 2 and kv_pool.window_blocks(130, 128) == 3
    ring = kv_pool.window_slot_bytes(cfg, 128)
    assert ring == kv_pool.slot_bytes(cfg, 128) == 6 * 2 * 128 * 4096  # 6 MiB a sequence
    per_block = kv_pool.bytes_per_block(128, cfg.kv_heads, cfg.head_dim, cfg.kv_layers)
    assert per_block == 128 * 8 * 1024  # 8 KiB a token: the two global layers
    budget = 2_000_000_000
    n = kv_pool.blocks_for_budget(budget, 128, cfg.kv_heads, cfg.head_dim, cfg.kv_layers,
                                  state_bytes=33 * ring)
    assert n == 1708
    by_kind = kv_pool.pool_bytes(n, 128, cfg.kv_heads, cfg.head_dim, cfg.kv_layers) + 33 * ring
    assert 0 <= budget - by_kind < per_block
    # a uniform pool of the same bytes: 32 KiB a token, a quarter of the tokens
    uniform = kv_pool.blocks_for_budget(budget, 128, cfg.kv_heads, cfg.head_dim, cfg.n_layers)
    assert n * 128 > 3.5 * uniform * 128
    # ... and the CLI sizes the pool so
    from deepspeed_tpu.inference.cli import engine_config_from_args, serve_parse_args

    args = serve_parse_args(["--model", "", "--kv-pool-bytes", str(budget), "--block-size", "128",
                             "--max-concurrent", "32", "--max-context", "10240",
                             "--max-blocks-per-seq", "80"])
    assert engine_config_from_args(args, cfg).kv_cache.num_blocks == 1708


# -- what names K/V blocks alone ----------------------------------------------
@pytest.mark.parametrize("what,kw", [
    ("kv_cache_dtype", {"kv_cache": {"kv_cache_dtype": "int8"}}),
    ("host block tier", {"kv_cache": {"prefix_cache": True, "host_tier_bytes": 1 << 20}}),
    ("speculative decoding", {"spec_k": 2}),
    ("quantized weights", {"quant": {"enabled": True, "bits": 8}}),
    ("tp_size=2", {"tp_size": 2}),
])
def test_what_cannot_carry_the_window_pool_is_refused_at_build(what, kw):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=what):
        _engine(cfg, params, **kw)


@pytest.mark.parametrize("call", [
    lambda e: e.export_kv_blocks([0]),
    lambda e: e.import_kv_blocks([0], {}),
    lambda e: e.import_kv_blocks_chunked([0], {}),
    lambda e: e.export_kv_blocks_device([0]),
    lambda e: e.export_kv_blocks_windows([0]),
    lambda e: e.import_kv_blocks_device([0], [], 8),
    lambda e: e.spec_round(2),
    lambda e: e._build_verify_step(2),
], ids=["export", "import", "import_chunked", "export_device", "export_windows",
        "import_device", "spec_round", "verify_step"])
def test_movers_of_kv_blocks_raise_rather_than_misread_the_window_pool(call):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="window pool"):
        call(_engine(cfg, params))


def test_prefix_cache_is_switched_off_with_one_log_line():
    cfg, params = _model()
    lines = []

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    from deepspeed_tpu.utils.logging import logger

    handler = Catch()
    logger.addHandler(handler)
    try:
        eng = _engine(cfg, params, kv_cache={"prefix_cache": True})
    finally:
        logger.removeHandler(handler)
    assert eng.prefix_cache is None
    assert sum("prefix cache switched off" in m for m in lines) == 1
    p = np.arange(1, 41, dtype=np.int32)
    a = eng.generate([p], max_new_tokens=3)[0]
    b = eng.generate([p], max_new_tokens=3)[0]
    np.testing.assert_array_equal(a, b)


def test_the_served_counters_count_the_cache_by_kind():
    """Through the serving driver: the step counters fold into the metrics,
    health() reports the rings, and an idle engine holds nothing."""
    from deepspeed_tpu.serving import SamplingParams, ServingDriver

    cfg, params = _model()
    eng = _engine(cfg, params)
    with ServingDriver(eng) as driver:
        reqs = [driver.submit(p, params=SamplingParams(max_new_tokens=12, ignore_eos=True))
                for p in _prompts((50, 9))]
        assert all(r.wait(120) for r in reqs)
        c = driver.metrics.counters
        steps = c["engine_steps_total"]
        assert 0 < c["kv_window_blocks_used_total"] <= steps * 2 * 3
        assert c["kv_global_blocks_used_total"] > 0 and c["kv_context_tokens_total"] > 0
        assert 0 < c["paged_window_live_blocks_total"] <= c["paged_live_blocks_total"] + 2 * steps
        # only the layers that have experts are layer calls: 7 of the 8
        assert c["moe_layer_calls_total"] % 7 == 0
    acct = eng.state_manager.kv_block_accounting()
    assert acct["live"] == acct["window_live"] == 0
