"""The plain LongCat-Flash reference against the published code it names, and the seven readers
PR 45 adds on hand-made counts and a hand-made trace. ``transformers`` has ``longcat_flash``
(``LongcatFlashForCausalLM``), so the whole yardstick is held to its source on tiny seeded
weights in float32: the double layer with its shortcut, both LoRA scales and the inner norms'
eps, interleaved rotary, the softmax router that chooses on probability + bias and weighs by the
probability x 6, real and identity experts, the untied head; then the chip's SHARE against the
same published model with the other chips' experts zeroed.

CPU, by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import numpy as np
import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.reference import longcat_flash

TOL = 2e-4  # float32 on both sides, logits of unit scale
CELL = "longcat-flash-chat.serve-tool-agent-closed64"
HF = Catalog().config("longcat-flash-chat")
V5E = "TPU v5 lite"
NEW = {"sat_mla_planes_decode_roofline_pct": "kernels", "sat_mla_chunk_time_pct": "kernels",
       "sat_latent_planes_bytes_per_token": "cache", "sat_latent_planes_fill_pct": "cache",
       "sat_scmoe_gmm_roofline_pct": "expert layer", "sat_moe_zero_pair_pct": "expert layer",
       "sat_moe_held_pair_pct": "expert layer"}
TINY = dict(vocab_size=128, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32,
            num_layers=2, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=6,
            zero_expert_num=3, moe_topk=3, routed_scaling_factor=6.0, rms_norm_eps=1e-5,
            rope_theta=10000000.0, max_position_embeddings=2048)


def _np(t):
    return t.detach().numpy()


def _published(seed=0):
    """(the published model on seeded weights, the same weights as the system's tree)."""
    import torch
    from transformers.models.longcat_flash import LongcatFlashConfig, LongcatFlashForCausalLM

    torch.manual_seed(seed)
    cfg = LongcatFlashConfig(**TINY, num_key_value_heads=4, head_dim=8, tie_word_embeddings=False,
                             attention_bias=False, attention_dropout=0.0)
    model = LongcatFlashForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at their identity would hide a norm in the wrong place
        for name, p in model.named_parameters():
            if "norm" in name:
                p.copy_(0.5 + torch.rand_like(p))
            elif "classifier" in name:
                p.copy_(torch.randn_like(p) * 0.5)
            elif p.ndim == 2:
                p.copy_(torch.randn_like(p) * p.shape[1] ** -0.5)
        for layer in model.model.layers:   # a buffer: zeros as built, so a bias that turns choices
            layer.mlp.router.e_score_correction_bias.copy_(torch.randn(9) * 0.05)
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    sub = {k: [] for k in ("attn_norm", "mlp_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                           "wkv_b", "wo", "w_gate", "w_up", "w_down")}
    top = {k: [] for k in ("router", "router_bias", "w_gate", "w_up", "w_down")}
    names = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    for l in range(2):
        p = f"model.layers.{l}"
        for i in range(2):
            sub["attn_norm"].append(sd[f"{p}.input_layernorm.{i}.weight"])
            sub["mlp_norm"].append(sd[f"{p}.post_attention_layernorm.{i}.weight"])
            for ours, theirs in (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
                                 ("wkv_b", "kv_b_proj"), ("wo", "o_proj")):
                sub[ours].append(sd[f"{p}.self_attn.{i}.{theirs}.weight"].T)
            sub["q_a_norm"].append(sd[f"{p}.self_attn.{i}.q_a_layernorm.weight"])
            sub["kv_a_norm"].append(sd[f"{p}.self_attn.{i}.kv_a_layernorm.weight"])
            for ours, theirs in names:
                sub[ours].append(sd[f"{p}.mlps.{i}.{theirs}.weight"].T)
        top["router"].append(sd[f"{p}.mlp.router.classifier.weight"].T)
        top["router_bias"].append(sd[f"{p}.mlp.router.e_score_correction_bias"])
        for ours, theirs in names:
            top[ours].append(np.stack([sd[f"{p}.mlp.experts.{e}.{theirs}.weight"].T for e in range(6)]))
    params = {"embed": sd["model.embed_tokens.weight"], "final_norm": sd["model.norm.weight"],
              "lm_head": sd["lm_head.weight"].T,
              "layers": {**{k: np.stack(v) for k, v in top.items()},
                         "sub": {k: np.stack(v) for k, v in sub.items()}}}
    return model, params


def _hf(**extra):
    return {**TINY, "model_type": "longcat_flash", "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
            "zero_expert_type": "identity", **extra}


def test_the_whole_model_against_longcat_flash_for_causal_lm():
    pytest.importorskip("transformers")
    import torch

    model, params = _published()
    toks = np.random.default_rng(1).integers(0, 128, size=48)
    with torch.no_grad():
        want = _np(model(torch.tensor(toks[None])).logits[0])
    got = np.asarray(longcat_flash.logits(params, toks, _hf()))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # every part of the block is in the comparison: identity and real experts are both chosen
    gates = longcat_flash.routing_weights(
        np.random.default_rng(2).normal(size=(64, 64)).astype(np.float32), params["layers"]["router"][0],
        params["layers"]["router_bias"][0], top_k=3, scale=6.0)
    chosen = np.asarray(gates) > 0
    assert chosen[:, :6].any() and chosen[:, 6:].any() and (chosen.sum(-1) == 3).all()


def test_a_share_against_the_published_model_with_the_other_experts_zeroed():
    """Share 1 of 3 (experts 2-3): the published model whose other experts' down projections
    are zeroed computes what this chip adds: its own experts' part and the identity part."""
    pytest.importorskip("transformers")
    import torch

    model, params = _published()
    with torch.no_grad():
        for layer in model.model.layers:
            for e in (0, 1, 4, 5):
                layer.mlp.experts[e].down_proj.weight.zero_()
    toks = np.random.default_rng(3).integers(0, 128, size=40)
    with torch.no_grad():
        want = _np(model(torch.tensor(toks[None])).logits[0])
    cut = dict(params, layers={k: (v[:, 2:4] if k in ("w_gate", "w_up", "w_down") else v)
                               for k, v in params["layers"].items()})
    hf = _hf(n_routed_experts=2,
             deployment_share={"n_routed_experts": 6, "chips_per_layer": 3, "share_index": 1})
    np.testing.assert_allclose(np.asarray(longcat_flash.logits(cut, toks, hf)), want, atol=TOL, rtol=0)


def test_the_reference_refuses_what_it_is_not():
    _, params = (None, {"embed": np.zeros((4, 4), np.float32)})
    for change in ({"model_type": "axk1"}, {"tie_word_embeddings": True}, {"norm_topk_prob": True},
                   {"zero_expert_type": "copy"}, {"rope_scaling": {"type": "yarn", "factor": 2}}):
        with pytest.raises(ValueError):
            longcat_flash.hidden(params, np.zeros(4, np.int32), _hf(**change))


# -- the readers ------------------------------------------------------------------------------------
def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, names=True, hf=HF):
    """A window of 100 steps of 4 expert calls each, its last 3 s traced with 30 launches."""
    before = {"engine_steps_total": 10}
    after = {"engine_steps_total": 110}
    if counters:
        before.update(latent_decode_blocks_total=0, latent_live_blocks_total=0, moe_layer_calls_total=0,
                      moe_routed_rows_total=0, moe_experts_hit_total=0, moe_pairs_total=0,
                      moe_zero_pairs_total=0, moe_held_pairs_total=0)
        after.update(latent_decode_blocks_total=100 * 1300, latent_live_blocks_total=100 * 1500,
                     moe_layer_calls_total=400, moe_routed_rows_total=400 * 70, moe_experts_hit_total=400 * 9,
                     moe_pairs_total=400 * 3360, moe_zero_pairs_total=400 * 1120, moe_held_pairs_total=400 * 70)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    ops = [["fusion:kOutput", 0.9]]
    if names:
        ops += [["dstpu_mla_decode.3", 0.2], ["dstpu_mla_decode.9", 0.1], ["dstpu_mla_chunk", 0.5],
                ["dstpu_moe_gmm", 0.4]]
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}} if trace else None}


def test_a_plane_a_call_and_two_planes_a_layer():
    m = importlib.import_module("benchmarks.metrics.sat_mla_planes_decode_roofline_pct")
    assert m.planes(HF) == 8
    block = 128 * 576 * 2
    assert m.bytes(1, HF, 128) == block == 147_456
    assert m.ops(1, HF, 128) == 128 * 64 * (576 + 512) * 2
    peak = peaks.device_peaks(V5E)
    assert m.bytes(1, HF, 128) / peak.hbm_bytes_s > m.ops(1, HF, 128) / peak.bf16_flops   # the bytes bound it
    want = 100.0 * 30 * 8 * (1300 * block / peak.hbm_bytes_s) / 0.3
    assert reader("sat_mla_planes_decode_roofline_pct")(record()) == pytest.approx(want)
    assert want < 100.0


def test_the_caches_price_and_fill_are_by_planes():
    assert reader("sat_latent_planes_bytes_per_token")(record()) == 8 * 576 * 2 == 9216
    blocks = 3_000_000_000 // (8 * 147_456) - 1
    assert blocks == 2542
    assert reader("sat_latent_planes_fill_pct")(record()) == pytest.approx(100.0 * 1500 / blocks)


def test_the_expert_matmuls_least_time_is_the_hit_experts_bytes():
    m = importlib.import_module("benchmarks.metrics.sat_scmoe_gmm_roofline_pct")
    expert = 3 * 6144 * 2048
    assert m.bytes(70, 9, HF) == 2 * (9 * expert + 3 * 70 * (6144 + 2048))
    assert m.ops(70, HF) == 3 * 2.0 * 70 * 6144 * 2048
    peak = peaks.device_peaks(V5E)
    want = 100.0 * 30 * 4 * (m.bytes(70, 9, HF) / peak.hbm_bytes_s) / 0.4
    assert reader("sat_scmoe_gmm_roofline_pct")(record()) == pytest.approx(want)


def test_shares_of_the_pairs_and_of_the_busy_time():
    assert reader("sat_moe_zero_pair_pct")(record()) == pytest.approx(100.0 / 3)
    assert reader("sat_moe_held_pair_pct")(record()) == pytest.approx(100.0 * 70 / 3360)
    assert reader("sat_mla_chunk_time_pct")(record()) == pytest.approx(100.0 * 0.5 / 2.5)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counters_or_the_kernel_reads_nothing(name):
    """The parent's program, or another configuration's: None, and no exception."""
    r = reader(name)
    traced = "roofline" in name or "time" in name
    if traced:
        assert r(record(trace=False)) is None
        assert r(record(names=False)) is None
    if "time" not in name:
        assert r(record(counters=False)) is None
        assert r(record(hf=Catalog().config("a.x-k1"))) is None
        assert r(record(hf=Catalog().config("qwen3-1.7b"))) is None


def test_the_new_metrics_are_declared_for_the_cell():
    """Each new metric lists ITS cell (and may come to list others), at its layer."""
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name, layer in NEW.items():
        assert CELL in index[name]["workloads"] and index[name]["layer"] == layer
        assert index[name]["moves"] == "gen_tok_s"
    assert CELL in index["sat_mla_decode_time_pct"]["workloads"]
    e2e = {m["name"]: m for m in Catalog().index["end_to_end"]}
    assert CELL in e2e["gen_tok_s"]["workloads"]
