"""The plain K-EXAONE reference against the published code it names. ``transformers`` 4.57 has
no ``exaone_moe``; it has both halves the configuration's keys point at, so each half of the
yardstick is held to its source on tiny seeded weights in float32:

  * the attention half and the residual path against ``Exaone4ForCausalLM`` (the family's hybrid
    model, ``modeling_exaone4.py``): RMSNorm on each block's OUTPUT, per-head q/k norm, rotary on
    the sliding layers only, the window's convention, the untied head, with every MLP dense;
  * the expert block against ``DeepseekV3MoE`` (``modeling_deepseek_v3.py``): sigmoid scores,
    the selection bias that chooses and does not weigh, top-k renormalised x
    ``routed_scaling_factor``, the ungated shared expert.

CPU, by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import numpy as np
import pytest

from benchmarks.reference import k_exaone

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402

TOL = 2e-4  # float32 on both sides, logits of unit scale


def _np(t):
    return t.detach().numpy()


def test_attention_and_residual_path_against_exaone4():
    from transformers.models.exaone4 import Exaone4Config, Exaone4ForCausalLM

    torch.manual_seed(0)
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    cfg = Exaone4Config(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
        rope_theta=1e6, sliding_window=16, sliding_window_pattern=4, layer_types=kinds * 2,
        max_position_embeddings=256, tie_word_embeddings=False, attention_dropout=0.0)
    model = Exaone4ForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at their identity would hide a norm in the wrong place
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.2 * torch.randn_like(p))
    sd = model.state_dict()
    L = 8

    def stack(fmt, t=False):
        return np.stack([_np(sd[fmt.format(i)]).T if t else _np(sd[fmt.format(i)]) for i in range(L)])

    layers = {f"w{n}": stack(f"model.layers.{{}}.self_attn.{n}_proj.weight", True) for n in "qkvo"}
    layers |= {
        "q_norm": stack("model.layers.{}.self_attn.q_norm.weight"),
        "k_norm": stack("model.layers.{}.self_attn.k_norm.weight"),
        "attn_norm": stack("model.layers.{}.post_attention_layernorm.weight"),
        "mlp_norm": stack("model.layers.{}.post_feedforward_layernorm.weight"),
        "lead": {f"w_{n}": stack(f"model.layers.{{}}.mlp.{n}_proj.weight", True)
                 for n in ("gate", "up", "down")},
    }
    params = {"embed": _np(sd["model.embed_tokens.weight"]), "layers": layers,
              "final_norm": _np(sd["model.norm.weight"]), "lm_head": _np(sd["lm_head.weight"]).T}
    hf = {"model_type": "exaone_moe", "num_hidden_layers": L, "layer_types": kinds * 2,
          "mlp_layer_types": ["dense"] * L, "sliding_window": 16, "num_attention_heads": 4,
          "num_key_value_heads": 2, "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6},
          "num_experts_per_tok": 2, "routed_scaling_factor": 2.5, "num_experts": 4,
          "scoring_func": "sigmoid", "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "tie_word_embeddings": False}
    toks = np.random.default_rng(0).integers(0, 128, size=(2, 70)).astype(np.int32)  # > 4 windows
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got = np.stack([np.asarray(k_exaone.logits(params, row, hf)) for row in toks])
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    np.testing.assert_allclose(
        k_exaone.logits(params, toks[0], hf, rows=[3, 69]), got[0][[3, 69]], atol=1e-6)


@pytest.mark.parametrize("shared", [1, 2])
def test_expert_block_against_deepseek_v3(shared):
    from transformers.models.deepseek_v3 import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3MoE

    torch.manual_seed(1)
    cfg = DeepseekV3Config(
        hidden_size=64, moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=3,
        n_shared_experts=shared, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, hidden_act="silu")
    moe = DeepseekV3MoE(cfg).eval()
    with torch.no_grad():   # (the published init leaves the router's weight unset and the bias 0)
        moe.gate.weight.copy_(torch.randn(16, 64))
        moe.gate.e_score_correction_bias.copy_(0.3 * torch.randn(16))
    x = torch.randn(1, 24, 64)
    with torch.no_grad():
        want = moe(x)[0].numpy()

    def experts(name):
        return np.stack([_np(getattr(e, name).weight).T for e in moe.experts])[None]

    stacks = {
        "router": _np(moe.gate.weight).T[None], "router_bias": _np(moe.gate.e_score_correction_bias)[None],
        "w_gate": experts("gate_proj"), "w_up": experts("up_proj"), "w_down": experts("down_proj"),
        "shared_gate": _np(moe.shared_experts.gate_proj.weight).T[None],
        "shared_up": _np(moe.shared_experts.up_proj.weight).T[None],
        "shared_down": _np(moe.shared_experts.down_proj.weight).T[None],
    }
    import jax
    import jax.numpy as jnp

    stacks = {k: jnp.asarray(v) for k, v in stacks.items()}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(k_exaone.sparse_mlp(np.asarray(x[0]), stacks, 0, top_k=3, scale=2.5, first=0))
        # a share: experts 4-7 alone, the shared expert still whole
        part = {k: (v[:, 4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in stacks.items()}
        mine = np.asarray(k_exaone.sparse_mlp(np.asarray(x[0]), part, 0, top_k=3, scale=2.5, first=4))
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    assert 1e-3 < np.max(np.abs(mine - got))  # the other experts' part is left out
    w = np.asarray(k_exaone.routing_weights(
        np.asarray(x[0]), stacks["router"][0], stacks["router_bias"][0], 3, 2.5))
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    assert ((w > 0).sum(-1) == 3).all()


def test_layer_plan_is_the_head_of_the_published_lists():
    hf = {"num_hidden_layers": 8, "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 12,
          "mlp_layer_types": ["dense"] + ["sparse"] * 47}
    plan = k_exaone.layer_plan(hf)
    assert len(plan) == 8 and plan[0] == ("sliding_attention", "dense")
    assert [i for i, (k, _) in enumerate(plan) if k == "full_attention"] == [3, 7]
    with pytest.raises(ValueError):
        k_exaone.layer_plan({**hf, "num_hidden_layers": 49})


def test_the_reference_refuses_what_it_is_not():
    ok = {"model_type": "exaone_moe", "scoring_func": "sigmoid"}
    for bad in ({"model_type": "qwen3_next"}, {**ok, "tie_word_embeddings": True},
                {**ok, "n_group": 2}, {**ok, "scoring_func": "softmax"}):
        with pytest.raises(ValueError):
            k_exaone.hidden({}, [0], bad)
