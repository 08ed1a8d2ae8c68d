"""The plain A.X-K1 reference against the published code it names, and the five readers PR 37
adds on hand-made counts and a hand-made trace. ``transformers`` has no ``axk1``; it has
DeepseekV3, whose key names the configuration carries, so each half of the yardstick is held to
its source on tiny seeded weights in float32:

  * latent attention and the residual path against ``DeepseekV3ForCausalLM`` with every MLP
    dense (``first_k_dense_replace`` = the depth): the two query projections with a norm
    between, the joint ``kv_a`` projection and its norm, ``kv_b`` split by head into keys and
    values, YaRN rotary in interleaved pairs on the rope dims alone, the softmax scale times
    mscale squared, the untied head;
  * the expert block against ``DeepseekV3MoE`` / ``DeepseekV3TopkRouter``, which always has a
    selection bias: the top-two-sum group rule, the choice inside the kept groups, the weights
    renormalised x ``routed_scaling_factor``, the ungated shared expert. The maximum rule of a
    router WITHOUT a bias (A.X-K1's ``topk_method "none"``) has no published code in
    ``transformers``; it is held to a few lines of numpy here.

CPU, by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import numpy as np
import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.reference import axk1

TOL = 2e-4  # float32 on both sides, logits of unit scale
CELL = "a.x-k1.serve-doc-long-closed64"
HF = Catalog().config("a.x-k1")
V5E = "TPU v5 lite"
NEW = {"sat_mla_decode_time_pct": "kernels", "sat_mla_decode_roofline_pct": "kernels",
       "sat_latent_fill_pct": "cache", "sat_latent_bytes_per_token": "cache",
       "sat_moe_lead_gmm_roofline_pct": "expert layer", "sat_moe_group_hit_pct": "expert layer"}
YARN = {"type": "yarn", "factor": 32, "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def _np(t):
    return t.detach().numpy()


def test_attention_and_residual_path_against_deepseek_v3():
    pytest.importorskip("transformers")
    import torch
    from transformers.models.deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM

    torch.manual_seed(0)
    L = 3
    cfg = DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=L, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense_replace=L, n_routed_experts=8, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling={**YARN, "rope_type": "yarn"}, rope_interleave=True,
        max_position_embeddings=2048, tie_word_embeddings=False, attention_bias=False,
        attention_dropout=0.0)
    model = DeepseekV3ForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at their identity would hide a norm in the wrong place
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.2 * torch.randn_like(p))
    sd = model.state_dict()

    def stack(fmt, t=False):
        return np.stack([_np(sd[fmt.format(i)]).T if t else _np(sd[fmt.format(i)]) for i in range(L)])

    attn = {"wq_a": "q_a_proj", "wq_b": "q_b_proj", "wkv_a": "kv_a_proj_with_mqa",
            "wkv_b": "kv_b_proj", "wo": "o_proj"}
    layers = {k: stack(f"model.layers.{{}}.self_attn.{v}.weight", True) for k, v in attn.items()}
    layers |= {
        "q_a_norm": stack("model.layers.{}.self_attn.q_a_layernorm.weight"),
        "kv_a_norm": stack("model.layers.{}.self_attn.kv_a_layernorm.weight"),
        "attn_norm": stack("model.layers.{}.input_layernorm.weight"),
        "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight"),
        "lead": {f"w_{n}": stack(f"model.layers.{{}}.mlp.{n}_proj.weight", True)
                 for n in ("gate", "up", "down")},
    }
    params = {"embed": _np(sd["model.embed_tokens.weight"]), "layers": layers,
              "final_norm": _np(sd["model.norm.weight"]), "lm_head": _np(sd["lm_head.weight"]).T}
    hf = {"model_type": "axk1", "num_hidden_layers": L, "first_k_dense_replace": L,
          "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000,
          "rope_scaling": YARN, "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
          "n_routed_experts": 8, "scoring_func": "sigmoid", "norm_topk_prob": True,
          "tie_word_embeddings": False}
    toks = np.random.default_rng(0).integers(0, 128, size=(2, 150)).astype(np.int32)  # > 2 x 64
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got = np.stack([np.asarray(axk1.logits(params, row, hf)) for row in toks])
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    np.testing.assert_allclose(
        axk1.logits(params, toks[0], hf, rows=[3, 149]), got[0][[3, 149]], atol=1e-6)
    m = 0.1 * np.log(32) + 1
    assert abs(axk1.softmax_scale(hf) - 24 ** -0.5 * m * m) < 1e-12


@pytest.mark.parametrize("shared", [1, 2])
def test_grouped_expert_block_against_deepseek_v3(shared):
    pytest.importorskip("transformers")
    import jax
    import jax.numpy as jnp
    import torch
    from transformers.models.deepseek_v3 import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3MoE

    torch.manual_seed(1)
    cfg = DeepseekV3Config(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=24, num_experts_per_tok=8,
        n_shared_experts=shared, n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5, hidden_act="silu")
    moe = DeepseekV3MoE(cfg).eval()
    with torch.no_grad():   # (the published init leaves the router's weight unset and the bias 0)
        moe.gate.weight.copy_(torch.randn(24, 64))
        moe.gate.e_score_correction_bias.copy_(0.2 * torch.randn(24))
    x = torch.randn(1, 40, 64)
    with torch.no_grad():
        want = moe(x)[0].numpy()

    def experts(name):
        return np.stack([_np(getattr(e, name).weight).T for e in moe.experts])[None]

    stacks = {k: jnp.asarray(v) for k, v in {
        "router": _np(moe.gate.weight).T[None], "router_bias": _np(moe.gate.e_score_correction_bias)[None],
        "w_gate": experts("gate_proj"), "w_up": experts("up_proj"), "w_down": experts("down_proj"),
        "shared_gate": _np(moe.shared_experts.gate_proj.weight).T[None],
        "shared_up": _np(moe.shared_experts.up_proj.weight).T[None],
        "shared_down": _np(moe.shared_experts.down_proj.weight).T[None]}.items()}
    kw = dict(top_k=8, scale=2.5, n_group=8, topk_group=4)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(axk1.sparse_mlp(np.asarray(x[0]), stacks, 0, first=0, **kw))
        # a share: group 2 (experts 6-8) alone, the shared expert still whole
        part = {k: (v[:, 6:9] if k in ("w_gate", "w_up", "w_down") else v) for k, v in stacks.items()}
        mine = np.asarray(axk1.sparse_mlp(np.asarray(x[0]), part, 0, first=6, **kw))
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    assert 1e-3 < np.max(np.abs(mine - got))  # the other groups' part is left out


def test_the_maximum_rule_of_a_router_without_a_bias():
    """No published code in ``transformers`` takes it: held to numpy. A group's score is its
    largest sigmoid; 4 of 8 groups; the 8 largest inside them; renormalised x 2.5."""
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(50, 24)).astype(np.float32) * 2
    w = np.asarray(axk1.routing_weights(logits, np.eye(24, dtype=np.float32), None, top_k=8,
                                        scale=2.5, n_group=8, topk_group=4))
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    for t in range(50):
        groups = np.argsort(-s[t].reshape(8, 3).max(-1), kind="stable")[:4]
        inside = np.where(np.isin(np.arange(24) // 3, groups), s[t], -np.inf)
        top = np.argsort(-inside, kind="stable")[:8]
        want = np.zeros(24)
        want[top] = s[t, top] / s[t, top].sum() * 2.5
        np.testing.assert_allclose(w[t], want, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    assert ((w > 0).sum(-1) == 8).all()


def test_yarn_frequencies_lie_between_their_own_and_a_thirty_second():
    inv, factor = axk1.yarn_inv_freq(64, 10000.0, HF["rope_scaling"])
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    assert factor == 1.0 and inv.shape == (32,)
    np.testing.assert_allclose(inv[:8], base[:8], rtol=1e-6)          # fast dims keep their own
    np.testing.assert_allclose(inv[-4:], base[-4:] / 32, rtol=1e-6)   # slow dims are interpolated
    assert (inv <= base * (1 + 1e-6)).all() and (inv >= base / 32 * (1 - 1e-6)).all()
    assert axk1.yarn_inv_freq(64, 10000.0, None)[1] == 1.0


def test_the_reference_refuses_what_it_is_not():
    ok = {"model_type": "axk1", "scoring_func": "sigmoid"}
    for bad in ({"model_type": "exaone_moe"}, {**ok, "tie_word_embeddings": True},
                {**ok, "scoring_func": "softmax"}, {**ok, "attention_bias": True},
                {**ok, "moe_layer_freq": 2}):
        with pytest.raises(ValueError):
            axk1.hidden({}, [0], bad)
    with pytest.raises(ValueError, match="YaRN"):
        axk1.yarn_inv_freq(64, 1e4, {"type": "linear", "factor": 2})


# -- the readers ------------------------------------------------------------------------------
def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, mla_s=0.3, gmm_s=1.2, hf=HF):
    """A 10 s window of 100 steps: 1,800 pool blocks held by the sequences a step (the pool full
    with what the prefix cache retains), 32 decode rows that walk 1,824
    latent blocks (57 each) in one layer's call; 400 expert-layer calls (4 expert layers) route
    33 pairs each and hit 18 of the 24 held experts; the last 3 s traced, 30 launches."""
    before = {"engine_steps_total": 10, "moe_layer_calls_total": 40, "moe_routed_rows_total": 1320,
              "moe_experts_hit_total": 720, "kv_global_blocks_used_total": 27110}
    after = {"engine_steps_total": 110, "moe_layer_calls_total": 440, "moe_routed_rows_total": 14520,
             "moe_experts_hit_total": 7920, "kv_global_blocks_used_total": 298210}
    if counters:
        before.update(latent_decode_blocks_total=18240, latent_decode_rows_total=320,
                      latent_live_blocks_total=18000, moe_group_tokens_total=4000,
                      moe_group_hit_tokens_total=2100)
        after.update(latent_decode_blocks_total=200640, latent_decode_rows_total=3520,
                     latent_live_blocks_total=198000, moe_group_tokens_total=44000,
                     moe_group_hit_tokens_total=22500)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None)]
    ops = [["fusion:kOutput", 0.4]]
    if gmm_s is not None:
        ops.insert(0, ["dstpu_moe_gmm custom-call:tpu_custom_call", gmm_s])
    if mla_s is not None:
        ops.insert(0, ["dstpu_mla_decode custom-call:tpu_custom_call", mla_s])
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


def test_a_blocks_bytes_and_operations():
    from benchmarks.metrics import sat_latent_bytes_per_token as per_token
    from benchmarks.metrics import sat_latent_fill_pct as fill
    from benchmarks.metrics import sat_mla_decode_roofline_pct as roofline
    from benchmarks.metrics import sat_moe_lead_gmm_roofline_pct as lead

    assert per_token.bytes(1, HF, 128) == 5 * 147456
    assert roofline.bytes(1, HF, 128) == 147456
    assert roofline.ops(1, HF, 128) == 128 * 64 * (576 + 512) * 2
    # ~121 operations a byte: half the v5e's ridge, so the bytes bound the kernel
    p = peaks.device_peaks(V5E)
    assert 120 < roofline.ops(1, HF, 128) / roofline.bytes(1, HF, 128) < 122 < p.bf16_flops / p.hbm_bytes_s / 1.9
    assert fill.pool_blocks(HF, Catalog().cell(CELL)["serve_args"]) == 2711
    assert lead.expert_layers(HF) == 4


def test_the_readers_on_recorded_counters():
    rec = record()
    assert reader("sat_latent_bytes_per_token")(rec) == 5760.0
    assert reader("sat_latent_fill_pct")(rec) == pytest.approx(100 * 1800 / 2711)
    assert reader("sat_moe_group_hit_pct")(rec) == pytest.approx(100 * 20400 / 40000)
    assert reader("sat_mla_decode_time_pct")(rec) == pytest.approx(100 * 0.3 / 2.5)
    # 30 launches x 5 layers x 1,824 blocks x 147,456 bytes at 819 GB/s over 0.3 s
    need = 30 * 5 * 1824 * 147456 / 819e9
    assert reader("sat_mla_decode_roofline_pct")(rec) == pytest.approx(100 * need / 0.3)
    assert 10 < 100 * need / 0.3 < 100
    from benchmarks.metrics.sat_moe_hit_gmm_roofline_pct import bytes as gmm_bytes
    want = 100 * 30 * 4 * gmm_bytes(33, 18, HF) / 819e9 / 1.2
    assert reader("sat_moe_lead_gmm_roofline_pct")(rec) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counters_or_the_kernel_reads_nothing(name):
    """The parent: no latent counters, no ``dstpu_mla_decode`` in its trace; an untraced run; a
    configuration of another kind. Nothing raises."""
    read = reader(name)
    if name != "sat_moe_lead_gmm_roofline_pct":
        assert read(record(counters=False, mla_s=None)) is None
    if NEW[name] != "cache" and name != "sat_moe_group_hit_pct":
        assert read(record(trace=False)) is None
    if name != "sat_mla_decode_time_pct":   # (the kernel's name in the trace is all that reads)
        assert read(record(hf=Catalog().config("k-exaone-236b-a23b"))) is None
        assert read(record(hf=Catalog().config("qwen3-1.7b"))) is None


def test_the_new_metrics_are_declared_for_the_cell():
    index = Catalog().index
    by_name = {m["name"]: m for m in index["per_layer"]}
    for name, layer in NEW.items():
        assert by_name[name]["layer"] == layer and by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "gen_tok_s"
    cell = Catalog().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("a.x-k1", "doc-long-closed64", 1)
    assert CELL in next(m for m in index["end_to_end"] if m["name"] == "gen_tok_s")["workloads"]
