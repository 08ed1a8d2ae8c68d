"""Per-architecture HF import parity (VERDICT round-2 missing #1).

Analogue of the reference's per-arch kernel-injection containers + v2
model_implementations coverage (module_inject/containers/,
inference/v2/model_implementations/{qwen_v2,qwen_v2_moe,falcon,phi,phi3}):
each supported architecture gets a tiny random HF checkpoint written with
``transformers`` and is checked for fp32 logits parity, a greedy decode, and
a train step through ``deepspeed_tpu.initialize``.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import load_hf_model, make_loss_fn
from deepspeed_tpu.models.transformer import forward

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402


def _save_tiny(tmp_path_factory, name, cfg_cls, model_cls, **cfg_kw):
    torch.manual_seed(0)
    cfg = cfg_cls(**cfg_kw)
    model = model_cls(cfg).eval()
    path = tmp_path_factory.mktemp(name)
    model.save_pretrained(path)
    return model, str(path)


@pytest.fixture(scope="module")
def tiny_qwen2(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_qwen2",
        transformers.Qwen2Config, transformers.Qwen2ForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_qwen2_moe(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_qwen2_moe",
        transformers.Qwen2MoeConfig, transformers.Qwen2MoeForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, shared_expert_intermediate_size=96,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        output_router_logits=False,
    )


@pytest.fixture(scope="module")
def tiny_falcon(tmp_path_factory):
    # falcon-7b shape: multi-query, parallel block, single shared layernorm
    return _save_tiny(
        tmp_path_factory, "hf_falcon",
        transformers.FalconConfig, transformers.FalconForCausalLM,
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False,
        max_position_embeddings=128,
    )


@pytest.fixture(scope="module")
def tiny_falcon40b_style(tmp_path_factory):
    # falcon-40b shape: GQA with interleaved fused qkv, dual layernorms
    return _save_tiny(
        tmp_path_factory, "hf_falcon40",
        transformers.FalconConfig, transformers.FalconForCausalLM,
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2, new_decoder_architecture=True,
        bias=False, alibi=False, max_position_embeddings=128,
    )


@pytest.fixture(scope="module")
def tiny_falcon_mha(tmp_path_factory):
    # legacy MHA falcon (falcon-rw shape): per-head [q_i,k_i,v_i] interleave
    return _save_tiny(
        tmp_path_factory, "hf_falcon_mha",
        transformers.FalconConfig, transformers.FalconForCausalLM,
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=False, parallel_attn=False,
        new_decoder_architecture=False, bias=True, alibi=False,
        max_position_embeddings=128,
    )


@pytest.fixture(scope="module")
def tiny_phi(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_phi",
        transformers.PhiConfig, transformers.PhiForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=128,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_mistral_headdim(tmp_path_factory):
    # mistral-nemo shape: head_dim decoupled from hidden/num_heads
    return _save_tiny(
        tmp_path_factory, "hf_mistral_hd",
        transformers.MistralConfig, transformers.MistralForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=128, tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_phi3(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_phi3",
        transformers.Phi3Config, transformers.Phi3ForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )


@pytest.fixture(scope="module")
def tiny_gpt2(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_gpt2",
        transformers.GPT2Config, transformers.GPT2LMHeadModel,
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, n_positions=128,
    )


@pytest.fixture(scope="module")
def tiny_opt(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_opt",
        transformers.OPTConfig, transformers.OPTForCausalLM,
        vocab_size=256, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        do_layer_norm_before=True, word_embed_proj_dim=64,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )


@pytest.fixture(scope="module")
def tiny_gemma(tmp_path_factory):
    # zero-centered rmsnorm, geglu MLP, sqrt(h) embed scaling, decoupled
    # head_dim, tied embeddings
    return _save_tiny(
        tmp_path_factory, "hf_gemma",
        transformers.GemmaConfig, transformers.GemmaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=128,
        pad_token_id=0, bos_token_id=1, eos_token_id=2,
    )


@pytest.fixture(scope="module")
def tiny_bloom(tmp_path_factory):
    # alibi positions, embedding layernorm, per-head qkv interleave, tied head
    return _save_tiny(
        tmp_path_factory, "hf_bloom",
        transformers.BloomConfig, transformers.BloomForCausalLM,
        vocab_size=256, hidden_size=64, n_layer=2, n_head=4,
    )


@pytest.fixture(scope="module")
def tiny_bloom_7heads(tmp_path_factory):
    # non-power-of-2 head count exercises the alibi slope interpolation rule
    return _save_tiny(
        tmp_path_factory, "hf_bloom7",
        transformers.BloomConfig, transformers.BloomForCausalLM,
        vocab_size=256, hidden_size=56, n_layer=2, n_head=7,
    )


@pytest.fixture(scope="module")
def tiny_gptj(tmp_path_factory):
    # interleaved (rotate_every_two) partial rotary, parallel block, biased head
    return _save_tiny(
        tmp_path_factory, "hf_gptj",
        transformers.GPTJConfig, transformers.GPTJForCausalLM,
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
        n_positions=128, tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_gptneox(tmp_path_factory):
    # parallel residual, fused qkv per-head interleave, partial rotary_pct
    return _save_tiny(
        tmp_path_factory, "hf_gptneox",
        transformers.GPTNeoXConfig, transformers.GPTNeoXForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.5,
        max_position_embeddings=128, use_parallel_residual=True,
    )


@pytest.fixture(scope="module")
def tiny_gptneox_seq(tmp_path_factory):
    # the sequential (use_parallel_residual=False) variant
    return _save_tiny(
        tmp_path_factory, "hf_gptneox_seq",
        transformers.GPTNeoXConfig, transformers.GPTNeoXForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=1.0,
        max_position_embeddings=128, use_parallel_residual=False,
    )


@pytest.fixture(scope="module")
def tiny_mixtral(tmp_path_factory):
    # block-sparse MoE: w1/w3/w2 experts, renormalized top-2 routing
    return _save_tiny(
        tmp_path_factory, "hf_mixtral",
        transformers.MixtralConfig, transformers.MixtralForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        output_router_logits=False,
    )


@pytest.fixture(scope="module")
def tiny_stablelm(tmp_path_factory):
    # LayerNorm + silu-GLU MLP + 0.25 partial rotary + qkv bias
    return _save_tiny(
        tmp_path_factory, "hf_stablelm",
        transformers.StableLmConfig, transformers.StableLmForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        partial_rotary_factor=0.25, use_qkv_bias=True,
        use_parallel_residual=False, max_position_embeddings=128,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_stablelm_parallel(tmp_path_factory):
    # parallel-residual variant: shared input_layernorm feeds both branches
    return _save_tiny(
        tmp_path_factory, "hf_stablelm_par",
        transformers.StableLmConfig, transformers.StableLmForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        partial_rotary_factor=0.25, use_qkv_bias=False,
        use_parallel_residual=True, max_position_embeddings=128,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_starcoder2(tmp_path_factory):
    # biased everything, non-GLU gelu MLP (c_fc/c_proj), tied embeddings
    return _save_tiny(
        tmp_path_factory, "hf_starcoder2",
        transformers.Starcoder2Config, transformers.Starcoder2ForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        use_bias=True, max_position_embeddings=128, tie_word_embeddings=True,
    )


@pytest.fixture(scope="module")
def tiny_gpt_neo(tmp_path_factory):
    # alternating global/local attention (window 8 < the 16-token test seq,
    # so the banded mask actually bites), unscaled logits (attn_scale=1.0),
    # plain Linears (no Conv1D), tied embeddings
    return _save_tiny(
        tmp_path_factory, "hf_gpt_neo",
        transformers.GPTNeoConfig, transformers.GPTNeoForCausalLM,
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        attention_types=[[["global", "local"], 1]], window_size=8,
        max_position_embeddings=128,
    )


@pytest.fixture(scope="module")
def tiny_internlm(tmp_path_factory):
    # InternLM = llama + biased q/k/v/o. transformers ships no InternLM class
    # (trust_remote_code upstream), but LlamaForCausalLM with
    # attention_bias=True is the same math and the same state-dict naming —
    # save that and stamp model_type=internlm the way the real checkpoints do.
    model, path = _save_tiny(
        tmp_path_factory, "hf_internlm",
        transformers.LlamaConfig, transformers.LlamaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        attention_bias=True, max_position_embeddings=128,
        tie_word_embeddings=False,
    )
    cfg_path = path + "/config.json"
    cfg = json.load(open(cfg_path))
    cfg["model_type"] = "internlm"
    cfg["bias"] = True
    json.dump(cfg, open(cfg_path, "w"))
    return model, path


@pytest.fixture(scope="module")
def tiny_llama_bias(tmp_path_factory):
    # llama's own attention_bias flag (no model_type patch)
    return _save_tiny(
        tmp_path_factory, "hf_llama_bias",
        transformers.LlamaConfig, transformers.LlamaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        attention_bias=True, max_position_embeddings=128,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_mistral_window(tmp_path_factory):
    # sliding_window=8 < the 16-token test seq: queries past position 8 must
    # NOT see the earliest keys (round-3 VERDICT: starcoder2 clamped instead)
    return _save_tiny(
        tmp_path_factory, "hf_mistral_window",
        transformers.MistralConfig, transformers.MistralForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=8, max_position_embeddings=128,
        tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_qwen3(tmp_path_factory):
    # per-head q/k RMSNorm + decoupled head_dim, no qkv bias
    return _save_tiny(
        tmp_path_factory, "hf_qwen3",
        transformers.Qwen3Config, transformers.Qwen3ForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=128, tie_word_embeddings=False,
    )


@pytest.fixture(scope="module")
def tiny_qwen3_moe(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_qwen3_moe",
        transformers.Qwen3MoeConfig, transformers.Qwen3MoeForCausalLM,
        vocab_size=256, hidden_size=64, moe_intermediate_size=48,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=128, tie_word_embeddings=False,
        output_router_logits=False,
    )


@pytest.fixture(scope="module")
def tiny_olmoe(tmp_path_factory):
    # q/k RMSNorm over the whole projection width, every layer sparse,
    # top-2 of 8 NOT renormalised, untied head (the published OLMoE shape)
    return _save_tiny(
        tmp_path_factory, "hf_olmoe",
        transformers.OlmoeConfig, transformers.OlmoeForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=48,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, tie_word_embeddings=False,
        output_router_logits=False,
    )


QWEN3_NEXT_TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=48, num_experts=8, num_experts_per_tok=3,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
    linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_value_head_dim=24,
    linear_num_key_heads=2, linear_num_value_heads=6,
    max_position_embeddings=128, tie_word_embeddings=False, output_router_logits=False,
)


@pytest.fixture(scope="module")
def tiny_qwen3_next(tmp_path_factory):
    # 3 Gated DeltaNet layers + 1 gated-attention layer, value heads 3 a key
    # head with a value width unlike the key's (so a q/k/v/z or b/a slice
    # taken in the wrong order cannot pass), partial rotary, top-3 of 8
    # renormalised + a shared expert. Norm weights and the DeltaNet's
    # dt_bias / A_log are moved off their initial values: a (1 + w) norm
    # read as w, or a plain norm read as (1 + w), would not show at init.
    torch.manual_seed(0)
    cfg = transformers.Qwen3NextConfig(**QWEN3_NEXT_TINY)
    model = transformers.Qwen3NextForCausalLM(cfg).eval()
    with torch.no_grad():
        for name, prm in model.named_parameters():
            if "norm" in name or name.endswith(("dt_bias", "A_log")):
                prm.add_(0.2 * torch.randn_like(prm))
    path = tmp_path_factory.mktemp("hf_qwen3_next")
    model.save_pretrained(path)
    return model, str(path)


JAMBA_TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
    num_experts=1, num_experts_per_tok=1, mamba_d_state=16, mamba_dt_rank=8, mamba_expand=2,
    mamba_d_conv=4, use_mamba_kernels=False, tie_word_embeddings=True, rms_norm_eps=1e-6,
    max_position_embeddings=128)


@pytest.fixture(scope="module")
def tiny_jamba(tmp_path_factory):
    # two periods of (mamba, mamba, attention, mamba), 4 query heads on ONE
    # key head, no positions, dense MLPs, a tied head; transformers' slow
    # path (use_mamba_kernels=False). The norms' weights (dt / B / C norms
    # among them), the conv's bias, A_log and D are moved off their initial
    # values: a norm or a term left out would not show at ones and zeros.
    torch.manual_seed(0)
    cfg = transformers.JambaConfig(**JAMBA_TINY)
    model = transformers.JambaForCausalLM(cfg).eval()
    with torch.no_grad():
        for name, prm in model.named_parameters():
            if "layernorm" in name or name.endswith(("conv1d.bias", "A_log", "mamba.D")):
                prm.add_(0.2 * torch.randn_like(prm))
    path = tmp_path_factory.mktemp("hf_jamba")
    model.save_pretrained(path)
    return model, str(path)


@pytest.fixture(scope="module")
def tiny_bert(tmp_path_factory):
    # post-LN bidirectional encoder + token types + masked-LM head
    return _save_tiny(
        tmp_path_factory, "hf_bert",
        transformers.BertConfig, transformers.BertForMaskedLM,
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=128, type_vocab_size=2,
    )


@pytest.fixture(scope="module")
def tiny_distilbert(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_distilbert",
        transformers.DistilBertConfig, transformers.DistilBertForMaskedLM,
        vocab_size=256, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
        max_position_embeddings=128,
    )


@pytest.fixture(scope="module")
def tiny_llama3_rope(tmp_path_factory):
    # llama-3.1-style frequency-banded rope scaling
    return _save_tiny(
        tmp_path_factory, "hf_llama3_rope",
        transformers.LlamaConfig, transformers.LlamaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 32,
        },
    )


@pytest.fixture(scope="module")
def tiny_linear_rope(tmp_path_factory):
    return _save_tiny(
        tmp_path_factory, "hf_linear_rope",
        transformers.LlamaConfig, transformers.LlamaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
        rope_scaling={"rope_type": "linear", "factor": 4.0},
    )


@pytest.fixture(scope="module")
def tiny_yarn_rope(tmp_path_factory):
    # yarn NTK-by-parts + attention_factor on cos/sin (deepseek/qwen long ctx)
    return _save_tiny(
        tmp_path_factory, "hf_yarn_rope",
        transformers.LlamaConfig, transformers.LlamaForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, tie_word_embeddings=False,
        rope_scaling={
            "rope_type": "yarn", "factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )


@pytest.fixture(scope="module")
def tiny_phi3_longrope(tmp_path_factory):
    # phi-3-128k-style longrope: per-dim short/long factor lists chosen by
    # sequence length vs the top-level original_max_position_embeddings
    dim_half = 8  # head_dim(16) // 2
    return _save_tiny(
        tmp_path_factory, "hf_phi3_longrope",
        transformers.Phi3Config, transformers.Phi3ForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, original_max_position_embeddings=32,
        tie_word_embeddings=False, pad_token_id=0, bos_token_id=1, eos_token_id=2,
        rope_scaling={
            "type": "longrope",  # phi3's config validator wants the legacy key
            "short_factor": [1.0 + 0.05 * i for i in range(dim_half)],
            "long_factor": [1.5 + 0.25 * i for i in range(dim_half)],
        },
    )


_FIXTURES = {
    "qwen2": "tiny_qwen2",
    "qwen2_moe": "tiny_qwen2_moe",
    "falcon": "tiny_falcon",
    "falcon40b": "tiny_falcon40b_style",
    "falcon_mha": "tiny_falcon_mha",
    "mistral_headdim": "tiny_mistral_headdim",
    "gpt2": "tiny_gpt2",
    "gemma": "tiny_gemma",
    "opt": "tiny_opt",
    "phi": "tiny_phi",
    "phi3": "tiny_phi3",
    "bloom": "tiny_bloom",
    "bloom7": "tiny_bloom_7heads",
    "gptj": "tiny_gptj",
    "gptneox": "tiny_gptneox",
    "gptneox_seq": "tiny_gptneox_seq",
    "mixtral": "tiny_mixtral",
    "stablelm": "tiny_stablelm",
    "stablelm_par": "tiny_stablelm_parallel",
    "starcoder2": "tiny_starcoder2",
    "gpt_neo": "tiny_gpt_neo",
    "internlm": "tiny_internlm",
    "llama_bias": "tiny_llama_bias",
    "mistral_window": "tiny_mistral_window",
    "bert": "tiny_bert",
    "distilbert": "tiny_distilbert",
    "qwen3": "tiny_qwen3",
    "qwen3_moe": "tiny_qwen3_moe",
    "olmoe": "tiny_olmoe",
    "qwen3_next": "tiny_qwen3_next",
    "jamba": "tiny_jamba",
}

# gpt_neo's attn_scale=1.0 skips the 1/sqrt(d) shrink and bert's post-LN
# renormalizes every residual add, so XLA:CPU's reduced-precision fp32
# matmuls leave ~1.5x more absolute noise in the logits (exact-precision
# parity is ~3e-6 / 2e-7 — verified while landing the arches)
_ATOL_OVERRIDES = {
    "gpt_neo": 6e-3,
    "bert": 6e-3,
    "distilbert": 6e-3,
    # reduced-precision CPU matmuls perturb the router softmax enough to
    # shift expert mixing weights (exact-precision parity is 7e-7)
    "qwen3_moe": 2e-2,
    # the same, over eight experts and a sigmoid-gated shared one
    "qwen3_next": 2e-2,
}


def _logits_parity(hf_model, path, atol=2e-3):
    cfg, params = load_hf_model(path, dtype="float32")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=atol, rtol=2e-3)
    return cfg, params


@pytest.mark.parametrize("kind", ["llama3", "linear", "yarn"])
def test_scaled_rope_logits_parity(kind, request):
    """Scaled-RoPE checkpoints (VERDICT round-3 missing #4: every llama-3.x /
    yarn / longrope checkpoint was refused) — fp32 logits parity at positions
    BEYOND the original pretraining length, where scaling actually bites."""
    hf_model, path = request.getfixturevalue(f"tiny_{kind}_rope")
    cfg, params = load_hf_model(path, dtype="float32")
    assert cfg.rope_scaling is not None and dict(cfg.rope_scaling)["rope_type"] == kind
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, size=(2, 96)).astype(np.int32)  # > original 32/64
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("seq", [16, 96])
def test_longrope_logits_parity(seq, request):
    """phi3 longrope switches short→long factor when the sequence crosses
    original_max_position_embeddings (32 here): parity on both sides."""
    hf_model, path = request.getfixturevalue("tiny_phi3_longrope")
    cfg, params = load_hf_model(path, dtype="float32")
    sc = dict(cfg.rope_scaling)
    assert sc["rope_type"] == "longrope" and sc["original_max_position_embeddings"] == 32
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 256, size=(2, seq)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=2e-3, rtol=2e-3)


def test_longrope_decode_crosses_boundary(request):
    """v1 engine generate with a KV cache must track the LIVE length for the
    longrope short/long switch (clen + s, not the cache capacity): greedy
    decode parity vs HF while generation crosses original_max (32)."""
    hf_model, path = request.getfixturevalue("tiny_phi3_longrope")
    from deepspeed_tpu.inference.v2.engine_factory import build_engine_v1

    engine = build_engine_v1(path, {"dtype": "float32", "max_out_tokens": 64})
    prompt = np.random.default_rng(3).integers(0, 256, size=(1, 28)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=10, do_sample=False
        ).numpy()[0]
    out = np.asarray(engine.generate(prompt, max_new_tokens=10))[0]
    np.testing.assert_array_equal(out[: len(ref)], ref)


def test_megatron_gpt_parity(tmp_path_factory, request):
    """Megatron-LM GPT state-dict naming + per-head-interleaved fused qkv:
    rewrite a tiny GPT-2's weights into the megatron layout and check the
    de-interleaving importer reproduces the GPT-2 logits exactly."""
    hf_model, _ = request.getfixturevalue("tiny_gpt2")
    sd = hf_model.state_dict()
    h = hf_model.config.n_embd
    nh = hf_model.config.n_head
    d = h // nh

    def meg_qkv(w_cols):  # [h, 3h] conv1d cols [q|k|v] → [3h, h] per-head rows
        q, k, v = (w_cols[:, i * h : (i + 1) * h].T for i in range(3))
        return (
            torch.stack([q.reshape(nh, d, h), k.reshape(nh, d, h), v.reshape(nh, d, h)], dim=1)
            .reshape(3 * h, h)
        )

    def meg_qkv_b(b_cols):  # [3h] → per-head interleave
        q, k, v = (b_cols[i * h : (i + 1) * h] for i in range(3))
        return torch.stack([q.reshape(nh, d), k.reshape(nh, d), v.reshape(nh, d)], dim=1).reshape(-1)

    meg = {
        "word_embeddings.weight": sd["transformer.wte.weight"],
        "position_embeddings.weight": sd["transformer.wpe.weight"],
        "transformer.final_layernorm.weight": sd["transformer.ln_f.weight"],
        "transformer.final_layernorm.bias": sd["transformer.ln_f.bias"],
    }
    for i in range(hf_model.config.n_layer):
        g, p = f"transformer.h.{i}", f"transformer.layers.{i}"
        meg[f"{p}.input_layernorm.weight"] = sd[f"{g}.ln_1.weight"]
        meg[f"{p}.input_layernorm.bias"] = sd[f"{g}.ln_1.bias"]
        meg[f"{p}.attention.query_key_value.weight"] = meg_qkv(sd[f"{g}.attn.c_attn.weight"])
        meg[f"{p}.attention.query_key_value.bias"] = meg_qkv_b(sd[f"{g}.attn.c_attn.bias"])
        meg[f"{p}.attention.dense.weight"] = sd[f"{g}.attn.c_proj.weight"].T.contiguous()
        meg[f"{p}.attention.dense.bias"] = sd[f"{g}.attn.c_proj.bias"]
        meg[f"{p}.post_attention_layernorm.weight"] = sd[f"{g}.ln_2.weight"]
        meg[f"{p}.post_attention_layernorm.bias"] = sd[f"{g}.ln_2.bias"]
        meg[f"{p}.mlp.dense_h_to_4h.weight"] = sd[f"{g}.mlp.c_fc.weight"].T.contiguous()
        meg[f"{p}.mlp.dense_h_to_4h.bias"] = sd[f"{g}.mlp.c_fc.bias"]
        meg[f"{p}.mlp.dense_4h_to_h.weight"] = sd[f"{g}.mlp.c_proj.weight"].T.contiguous()
        meg[f"{p}.mlp.dense_4h_to_h.bias"] = sd[f"{g}.mlp.c_proj.bias"]
    path = str(tmp_path_factory.mktemp("hf_megatron_gpt"))
    torch.save(meg, path + "/pytorch_model.bin")
    json.dump(
        {
            "model_type": "megatron_gpt",
            "vocab_size": hf_model.config.vocab_size,
            "hidden_size": h,
            "num_layers": hf_model.config.n_layer,
            "num_attention_heads": nh,
            "max_position_embeddings": hf_model.config.n_positions,
            "activation_function": "gelu_new",
        },
        open(path + "/config.json", "w"),
    )
    cfg, _ = _logits_parity(hf_model, path)
    assert cfg.tie_embeddings and cfg.position == "learned" and cfg.attn_qkv_bias


def test_clip_text_encoder_parity(tmp_path_factory):
    """CLIP's text tower (reference module_inject/containers/clip.py — the
    stable-diffusion text encoder): causal pre-LN encoder with quick_gelu;
    hidden-state parity via forward_hidden (CLIP has no LM head). atol is
    loose because XLA:CPU's reduced-precision fp32 matmuls meet ~3.2-scale
    activations here; exact-precision parity is 3.5e-6 (verified while
    landing the arch)."""
    torch.manual_seed(0)
    m = transformers.CLIPTextModel(
        transformers.CLIPTextConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=77,
        )
    ).eval()
    path = str(tmp_path_factory.mktemp("hf_clip_text"))
    m.save_pretrained(path)
    cfg, params = load_hf_model(path, dtype="float32")
    assert cfg.activation == "quick_gelu" and cfg.attn_causal
    toks = np.random.default_rng(21).integers(0, 256, size=(2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = m(torch.tensor(toks, dtype=torch.long)).last_hidden_state.numpy()
    from deepspeed_tpu.models.transformer import forward_hidden

    ours, _ = forward_hidden(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=5e-2, rtol=5e-3)


def test_bert_relu_mlm_parity(tmp_path_factory):
    """The cls.predictions transform uses the config's hidden activation —
    a relu checkpoint must not silently run gelu (code-review finding)."""
    hf_model, path = _save_tiny(
        tmp_path_factory, "hf_bert_relu",
        transformers.BertConfig, transformers.BertForMaskedLM,
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128, hidden_act="relu",
        max_position_embeddings=128, type_vocab_size=2,
    )
    _logits_parity(hf_model, path, atol=6e-3)


def test_bare_bert_model_loads(tmp_path_factory):
    """A bare BertModel checkpoint (root-level keys, no MLM head) loads with
    mlm_head=False; forward_hidden returns its final hidden states."""
    torch.manual_seed(0)
    m = transformers.BertModel(
        transformers.BertConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=128, type_vocab_size=2,
        ),
        add_pooling_layer=False,
    ).eval()
    path = str(tmp_path_factory.mktemp("hf_bert_bare"))
    m.save_pretrained(path)
    cfg, params = load_hf_model(path, dtype="float32")
    assert not cfg.mlm_head
    toks = np.random.default_rng(13).integers(0, 256, size=(2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = m(torch.tensor(toks, dtype=torch.long)).last_hidden_state.numpy()
    from deepspeed_tpu.models.transformer import forward_hidden

    ours, _ = forward_hidden(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=6e-3, rtol=2e-3)


def test_bert_token_type_parity(request):
    """token_type_ids flow into the stem sum before embeddings.LayerNorm —
    parity with HF on a mixed segment-A/segment-B batch."""
    hf_model, path = request.getfixturevalue("tiny_bert")
    cfg, params = load_hf_model(path, dtype="float32")
    rng = np.random.default_rng(11)
    toks = rng.integers(0, 256, size=(2, 16)).astype(np.int32)
    tt = np.zeros((2, 16), np.int32)
    tt[:, 8:] = 1
    with torch.no_grad():
        ref = hf_model(
            torch.tensor(toks, dtype=torch.long),
            token_type_ids=torch.tensor(tt, dtype=torch.long),
        ).logits.numpy()
    ours, _ = forward(params, jnp.asarray(toks), cfg, token_type_ids=jnp.asarray(tt))
    np.testing.assert_allclose(np.asarray(ours, np.float32), ref, atol=6e-3, rtol=2e-3)


def test_bert_mlm_train_step(request, devices8):
    """Masked-LM training through deepspeed_tpu.initialize on the 8-device
    mesh: explicit labels + loss_mask (split_lm_batch skips the causal shift
    when labels are given), loss decreases and stays finite."""
    _, path = request.getfixturevalue("tiny_bert")
    cfg, params = load_hf_model(path, dtype="float32")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 8},
            "steps_per_print": 1000,
        },
    )
    rng = np.random.default_rng(12)
    toks = rng.integers(0, 256, size=(8, 16)).astype(np.int32)
    labels = toks.copy()
    masked = toks.copy()
    mask = np.zeros((8, 16), np.float32)
    mask[:, [3, 7, 12]] = 1.0
    masked[:, [3, 7, 12]] = 103  # [MASK]-style corruption
    batch = {
        "input_ids": jnp.asarray(masked),
        "labels": jnp.asarray(labels),
        "loss_mask": jnp.asarray(mask),
    }
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_gpt_neo_windowed_decode(request):
    """Greedy decode with the KV cache where generation runs past the local
    window (8): the cached-path banded mask (q_glob vs cache positions) must
    match HF, including on the global layers of the alternating pattern."""
    hf_model, path = request.getfixturevalue("tiny_gpt_neo")
    from deepspeed_tpu.inference.v2.engine_factory import build_engine_v1

    engine = build_engine_v1(path, {"dtype": "float32", "max_out_tokens": 64})
    prompt = np.random.default_rng(7).integers(0, 256, size=(1, 6)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=10, do_sample=False
        ).numpy()[0]
    out = np.asarray(engine.generate(prompt, max_new_tokens=10))[0]
    np.testing.assert_array_equal(out[: len(ref)], ref)


@pytest.mark.parametrize("arch", sorted(_FIXTURES))
def test_logits_parity(arch, request):
    hf_model, path = request.getfixturevalue(_FIXTURES[arch])
    cfg, _ = _logits_parity(hf_model, path, atol=_ATOL_OVERRIDES.get(arch, 2e-3))
    if arch == "qwen2":
        assert cfg.attn_qkv_bias and not cfg.parallel_block
    elif arch == "qwen2_moe":
        assert cfg.n_experts == 4 and cfg.moe_shared_expert_dim == 96
        assert not cfg.moe_norm_topk_prob
    elif arch == "falcon":
        assert cfg.parallel_block and cfg.kv_heads == 1  # MQA
    elif arch == "falcon40b":
        assert cfg.kv_heads == 2  # GQA via interleaved fused qkv
    elif arch == "falcon_mha":
        # sequential block, biased projections, per-head qkv interleave
        assert not cfg.parallel_block and cfg.kv_heads == 4 and cfg.attn_qkv_bias
    elif arch == "phi":
        assert cfg.parallel_block and cfg.rope_frac == 0.5 and cfg.lm_head_bias
    elif arch == "phi3":
        assert not cfg.attn_qkv_bias  # fused qkv_proj split cleanly
    elif arch == "mistral_headdim":
        assert cfg.head_dim_override == 24 and cfg.head_dim == 24  # != 64/4
    elif arch == "gemma":
        assert cfg.norm == "rmsnorm_1p" and cfg.activation == "geglu"
        assert cfg.embed_scale and cfg.tie_embeddings and cfg.head_dim == 24
    elif arch == "gpt2":
        # Conv1D fused qkv split, learned positions, tied embeddings
        assert cfg.position == "learned" and cfg.tie_embeddings
    elif arch == "opt":
        assert cfg.activation == "relu" and cfg.position == "learned"
    elif arch.startswith("bloom"):
        assert cfg.position == "alibi" and cfg.embed_norm and cfg.tie_embeddings
    elif arch == "gptj":
        # interleaved partial rotary handled by the load-time permutation
        assert cfg.parallel_block and cfg.rope_frac == 0.5 and cfg.lm_head_bias
    elif arch == "gptneox":
        assert cfg.parallel_block and cfg.rope_frac == 0.5 and cfg.attn_qkv_bias
    elif arch == "gptneox_seq":
        assert not cfg.parallel_block and cfg.rope_frac == 1.0
    elif arch == "mixtral":
        assert cfg.n_experts == 4 and cfg.moe_top_k == 2 and cfg.moe_norm_topk_prob
    elif arch == "stablelm":
        assert cfg.norm == "layernorm" and cfg.activation == "swiglu"
        assert cfg.rope_frac == 0.25 and cfg.attn_qkv_bias
    elif arch == "stablelm_par":
        assert cfg.parallel_block and not cfg.attn_qkv_bias
    elif arch == "starcoder2":
        assert cfg.attn_out_bias and cfg.mlp_bias and cfg.tie_embeddings
        assert cfg.activation == "gelu"
    elif arch == "gpt_neo":
        # unscaled attention + alternating banded mask, window < test seq
        assert cfg.attn_scale == 1.0 and cfg.sliding_window == 8
        assert cfg.attn_layer_pattern == (0, 1)
        assert not cfg.attn_qkv_bias and cfg.attn_out_bias
    elif arch in ("internlm", "llama_bias"):
        assert cfg.attn_qkv_bias and cfg.attn_out_bias and cfg.norm == "rmsnorm"
    elif arch == "mistral_window":
        assert cfg.sliding_window == 8 and cfg.attn_layer_pattern is None
    elif arch in ("bert", "distilbert"):
        assert not cfg.attn_causal and cfg.norm_scheme == "post"
        assert cfg.mlm_head and not cfg.final_norm and cfg.embed_norm
        assert cfg.type_vocab_size == (2 if arch == "bert" else 0)
    elif arch == "qwen3":
        assert cfg.qk_norm and not cfg.attn_qkv_bias and cfg.head_dim == 24
    elif arch == "qwen3_moe":
        assert cfg.qk_norm and cfg.n_experts == 4 and cfg.moe_norm_topk_prob
        assert cfg.moe_shared_expert_dim == 0
    elif arch == "olmoe":
        assert cfg.qk_norm and cfg.qk_norm_kind == "rmsnorm_full"
        assert cfg.n_experts == 8 and cfg.moe_top_k == 2 and cfg.ffn_dim == 48
        assert not cfg.moe_norm_topk_prob and not cfg.tie_embeddings
    elif arch == "qwen3_next":
        assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "full") and cfg.hybrid
        assert cfg.norm == "rmsnorm_1p" and cfg.qk_norm and cfg.attn_out_gate
        assert cfg.rope_frac == 0.25 and cfg.head_dim == 32 and cfg.kv_layers == 1
        assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim) == (2, 6, 16, 24)
        assert cfg.n_experts == 8 and cfg.router_width == 8 and cfg.moe_top_k == 3
        assert cfg.moe_shared_expert_dim == 48 and cfg.ffn_dim == 32
    if cfg.n_experts:
        assert not cfg.moe_drop_tokens  # HF never drops a token


@pytest.mark.parametrize(
    "arch",
    ["qwen2_moe", "falcon", "phi", "gemma", "bloom", "gptj", "gptneox", "mixtral", "stablelm",
     "olmoe", "qwen3_next", "jamba"],
)
def test_greedy_decode_parity(arch, request):
    hf_model, path = request.getfixturevalue(_FIXTURES[arch])
    cfg, params = load_hf_model(path, dtype="float32")
    prompt = np.array([[5, 17, 42, 7]], dtype=np.int32)
    with torch.no_grad():
        hf_out = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=8, do_sample=False
        ).numpy()[0]
    toks = prompt.copy()
    # jitted: eight eager full forwards per arch dominated this test's time
    fwd = jax.jit(forward, static_argnames=("config",))
    for _ in range(8):
        logits, _ = fwd(params, jnp.asarray(toks), cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        toks = np.concatenate([toks, [[nxt]]], axis=1)
    np.testing.assert_array_equal(toks[0], hf_out)


@pytest.mark.parametrize("arch", ["qwen2", "qwen2_moe", "falcon", "phi", "phi3"])
def test_train_step_through_initialize(arch, request, devices8):
    _, path = request.getfixturevalue(_FIXTURES[arch])
    cfg, params = load_hf_model(path, dtype="float32")
    mesh = {"data": 4, "expert": 2} if cfg.n_experts else {"data": 8}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": mesh,
            "steps_per_print": 1000,
        },
    )
    toks = np.random.default_rng(0).integers(0, 256, size=(8, 33)).astype(np.int32)
    losses = [float(engine.train_batch(batch={"input_ids": toks})) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


def test_phi_qk_layernorm_parity(tmp_path_factory):
    """phi-1/2 qk_layernorm (one affine LayerNorm(head_dim) shared across
    heads — previously a hard refusal) imports as qk_norm_kind='layernorm'."""
    hf_model, path = _save_tiny(
        tmp_path_factory, "hf_phi_qk",
        transformers.PhiConfig, transformers.PhiForCausalLM,
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, qk_layernorm=True,
        partial_rotary_factor=0.5, max_position_embeddings=128,
    )
    cfg, _ = _logits_parity(hf_model, path)
    assert cfg.qk_norm and cfg.qk_norm_kind == "layernorm"


def test_stablelm2_qk_layernorm_parity(tmp_path_factory):
    """stablelm-2-12b class: per-head biasless q/k LayerNorms (previously a
    hard refusal) import as qk_norm_kind='layernorm_per_head'. HF's own
    _init_weights crashes on the biasless norms, so the tiny checkpoint is
    built with no_init_weights + manual randomization."""
    from transformers.modeling_utils import no_init_weights

    cfg_t = transformers.StableLmConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        qk_layernorm=True, partial_rotary_factor=0.25,
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    with no_init_weights():
        model = transformers.StableLmForCausalLM(cfg_t)
    torch.manual_seed(0)
    for p in model.parameters():
        p.data.normal_(0, 0.05)
    model = model.eval()
    path = str(tmp_path_factory.mktemp("hf_stablelm_qk"))
    model.save_pretrained(path)
    cfg, _ = _logits_parity(model, path)
    assert cfg.qk_norm and cfg.qk_norm_kind == "layernorm_per_head"


def test_gpt_neo_serves_v2_paged(request):
    """gpt_neo (alternating local/global pattern + unscaled logits) serves
    through the v2 paged engine: the layer stack unrolls with per-layer
    STATIC windows and the kernel takes the scale override — greedy parity
    vs HF."""
    hf_model, path = request.getfixturevalue("tiny_gpt_neo")
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine

    engine = build_hf_engine(path, {
        "dtype": "float32",
        "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
        "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
    })
    prompt = np.random.default_rng(9).integers(0, 256, size=(1, 12)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=8, do_sample=False
        ).numpy()[0]
    out = np.asarray(engine.generate([prompt[0]], max_new_tokens=8)[0])
    np.testing.assert_array_equal(out[: len(ref)], ref)


def test_qwen3_serves_v2_paged(request):
    """qwen3's per-head q/k RMSNorm must run in the PAGED layer body too
    (skipping it would silently diverge from the dense forward): greedy
    parity, v2 engine vs forward()."""
    hf_model, path = request.getfixturevalue("tiny_qwen3")
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine

    engine = build_hf_engine(path, {
        "dtype": "float32",
        "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
        "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
    })
    prompt = np.random.default_rng(5).integers(0, 256, size=(1, 12)).astype(np.int32)
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt, dtype=torch.long), max_new_tokens=6, do_sample=False
        ).numpy()[0]
    out = np.asarray(engine.generate([prompt[0]], max_new_tokens=6)[0])
    np.testing.assert_array_equal(out[: len(ref)], ref)


@pytest.mark.parametrize("arch", ["qwen2", "phi", "qwen3"])
def test_generate_through_inference_engine(arch, request):
    """init_inference path: checkpoint dir → v1 engine → generate."""
    _, path = request.getfixturevalue(_FIXTURES[arch])
    from deepspeed_tpu.inference.v2.engine_factory import build_engine_v1

    engine = build_engine_v1(path, {"dtype": "float32", "max_out_tokens": 16})
    prompt = np.array([[5, 17, 42, 7]], dtype=np.int32)
    out = engine.generate(prompt, max_new_tokens=6)
    out = np.asarray(out)
    assert out.shape[1] >= prompt.shape[1] + 6
    assert (out[:, : prompt.shape[1]] == prompt).all()


def test_engine_factory_dispatch(tiny_qwen2):
    _, path = tiny_qwen2
    arch = json.load(open(f"{path}/config.json"))["architectures"][0]
    assert arch == "Qwen2ForCausalLM"
    from deepspeed_tpu.inference.v2.engine_factory import load_model_implementation

    cfg, params = load_model_implementation(path, dtype="float32")
    assert cfg.attn_qkv_bias and params["layers"]["wq_b"].shape == (2, 64)


def test_unsupported_arch_raises(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps({"model_type": "mamba", "architectures": ["MambaForCausalLM"]}))
    with pytest.raises(ValueError, match="model_type"):
        load_hf_model(str(tmp_path))


def test_olmoe_clip_qkv_is_refused():
    from deepspeed_tpu.models.hf import config_from_hf

    hf = transformers.OlmoeConfig(num_hidden_layers=1, clip_qkv=8.0).to_dict()
    with pytest.raises(ValueError, match="clip_qkv"):
        config_from_hf(hf)


@pytest.mark.parametrize("arch", ["gpt2", "phi", "olmoe"])
def test_v2_engine_serves_biased_archs(arch, request):
    """The v2 paged engine must honor attention biases, partial rotary, the
    parallel block, learned positions, and (olmoe) the whole-width q/k norm
    and the expert layer — its layer_step is a separate
    implementation from the training forward, so parity is asserted against
    the HF greedy decode through the FULL continuous-batching path."""
    hf_model, path = request.getfixturevalue(_FIXTURES[arch])
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import load_hf_model

    cfg, params = load_hf_model(path, dtype="float32")
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
        "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
    })
    engine = InferenceEngineV2(cfg, params, rc)
    prompt = np.array([5, 17, 42, 7], dtype=np.int32)
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt[None], dtype=torch.long), max_new_tokens=6, do_sample=False
        ).numpy()[0]
    out = engine.generate([prompt], max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out[0]), ref)


def test_qwen3_next_fused_projections_split_by_use(tiny_qwen3_next):
    """The extractor's regrouping of in_proj_qkvz / in_proj_ba (laid out by
    KEY head) and q_proj (a query and a gate a head), against the slices
    transformers' fix_query_key_value_ordering takes."""
    hf_model, path = tiny_qwen3_next
    cfg, params = load_hf_model(path, dtype="float32")
    lin = hf_model.model.layers[1].linear_attn
    x = torch.randn(1, 5, 64)
    with torch.no_grad():
        q, k, v, z, b, a = lin.fix_query_key_value_ordering(lin.in_proj_qkvz(x), lin.in_proj_ba(x))
    gdn = {n: np.asarray(w[1]) for n, w in params["layers"]["gdn"].items()}
    xn = x[0].numpy()
    want = np.concatenate([t[0].reshape(5, -1).numpy() for t in (q, k, v)], axis=-1)
    np.testing.assert_allclose(xn @ gdn["gdn_qkv"], want, atol=1e-5)
    np.testing.assert_allclose(xn @ gdn["gdn_z"], z[0].reshape(5, -1).numpy(), atol=1e-5)
    np.testing.assert_allclose(
        xn @ gdn["gdn_ba"], np.concatenate([b[0].numpy(), a[0].numpy()], axis=-1), atol=1e-5)
    np.testing.assert_allclose(gdn["gdn_conv"].T, lin.conv1d.weight[:, 0].detach().numpy())
    att = hf_model.model.layers[3].self_attn
    with torch.no_grad():
        qg = att.q_proj(x).view(1, 5, 4, 64)
    full = {n: np.asarray(w[0]) for n, w in params["layers"]["full"].items()}
    np.testing.assert_allclose(xn @ full["wq"], qg[0, :, :, :32].reshape(5, -1).numpy(), atol=1e-5)
    np.testing.assert_allclose(xn @ full["wq_gate"], qg[0, :, :, 32:].reshape(5, -1).numpy(), atol=1e-5)


def test_qwen3_next_expert_share_loads_its_own_experts(tiny_qwen3_next, tmp_path):
    """``deployment_share`` in config.json: the router whole, the chip's own
    experts (share 1 of 4: experts 2-3 of 8), and a share that does not
    divide the published count refused."""
    import json
    import shutil

    from deepspeed_tpu.models.hf import config_from_hf

    hf_model, path = tiny_qwen3_next
    shutil.copytree(path, tmp_path / "share", dirs_exist_ok=True)
    hf = json.load(open(tmp_path / "share" / "config.json"))
    hf.update(num_experts=2, deployment_share={"num_experts": 8, "chips_per_layer": 4, "share_index": 1})
    json.dump(hf, open(tmp_path / "share" / "config.json", "w"))
    cfg, params = load_hf_model(str(tmp_path / "share"), dtype="float32")
    assert (cfg.n_experts, cfg.router_width, cfg.moe_expert_shard) == (2, 8, 1)
    assert params["layers"]["router"].shape == (4, 64, 8)
    assert params["layers"]["w_up"].shape == (4, 2, 64, 32)
    mlp = hf_model.model.layers[2].mlp
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["w_down"][2, 1]), mlp.experts[3].down_proj.weight.detach().numpy().T)
    with pytest.raises(ValueError, match="not one chip's share"):
        config_from_hf({**hf, "num_experts": 3})


def test_jamba_layers_by_kind_and_the_reference_agree(tiny_jamba):
    """The loader stacks a Jamba checkpoint by kind (the conv's weight [d, 1,
    K] -> [K, d], A_log [d, N] -> [N, d]), ``forward()`` and the benchmark's
    plain reference (benchmarks/reference/jamba.py) both equal transformers'
    ``JambaForCausalLM`` (slow path) on logits, over more tokens than a chunk
    of the scan kernel holds."""
    import importlib
    import json
    import os

    ref = importlib.import_module("benchmarks.reference.jamba")
    hf_model, path = tiny_jamba
    cfg, params = load_hf_model(path, dtype="float32")
    assert cfg.layer_kinds == ("mamba", "mamba", "full", "mamba") * 2
    assert cfg.position == "none" and cfg.kv_heads == 1 and cfg.tie_embeddings
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank) == (128, 16, 8)
    mixer = hf_model.model.layers[1].mamba
    m = {n: np.asarray(w[1]) for n, w in params["layers"]["mamba"].items()}
    np.testing.assert_array_equal(m["mamba_conv"].T, mixer.conv1d.weight[:, 0].detach().numpy())
    np.testing.assert_array_equal(m["mamba_a_log"].T, mixer.A_log.detach().numpy())
    np.testing.assert_array_equal(m["mamba_in"].T, mixer.in_proj.weight.detach().numpy())
    assert params["layers"]["full"]["wk"].shape == (2, 64, 16)
    tokens = np.random.default_rng(3).integers(0, 256, size=(2, 70)).astype(np.int32)
    with torch.no_grad():
        want = hf_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    with jax.default_matmul_precision("highest"):
        ours, _ = forward(params, jnp.asarray(tokens), cfg)
    # float32 on both sides: measured 7e-7 on logits of scale 0.8
    np.testing.assert_allclose(np.asarray(ours), want, atol=2e-5, rtol=0)
    hf = json.load(open(os.path.join(path, "config.json")))
    got = np.stack([np.asarray(ref.logits(params, row, hf)) for row in tokens])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(ref.logits(params, tokens[0], hf, rows=[3, 69]), got[0][[3, 69]], atol=1e-6)


def test_jamba_with_experts_is_refused_with_its_reason():
    from deepspeed_tpu.models.hf import config_from_hf

    hf = transformers.JambaConfig(**{**JAMBA_TINY, "num_experts": 4, "num_experts_per_tok": 2}).to_dict()
    with pytest.raises(ValueError, match="num_experts=4"):
        config_from_hf(hf)
    with pytest.raises(ValueError, match="leaves one kind"):
        config_from_hf({**transformers.JambaConfig(**JAMBA_TINY).to_dict(), "attn_layer_offset": 9})


# --- kimi_linear: no published code in transformers 4.57; the configuration's
# mapping, the three lifted refusals and the checkpoint names' round trip -------
KIMI_TINY = dict(
    model_type="kimi_linear", vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
    num_experts=2, num_experts_per_token=2, num_shared_experts=1, first_k_dense_replace=1,
    moe_layer_freq=1, moe_renormalize=True, moe_router_activation_func="sigmoid",
    num_expert_group=1, topk_group=1, use_grouped_topk=True, routed_scaling_factor=2.446,
    kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_use_nope=True, rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    tie_word_embeddings=False, hidden_act="silu", head_dim=16, model_max_length=512,
    num_nextn_predict_layers=0,
    linear_attn_config=dict(full_attn_layers=[2, 5], kda_layers=[1, 3, 4], head_dim=16,
                            num_heads=4, short_conv_kernel_size=4),
    deployment_share=dict(num_experts=6, chips_per_layer=3, share_index=1),
)


def test_kimi_linear_config_of_the_published_row_and_of_the_benchmarks_file():
    """The catalog row's ``config`` gives 20 KDA and 7 latent layers, a lead
    layer, 256 sigmoid experts top 8 with a bias and a shared expert, one query
    projection and no positions; the benchmark's file the 12-layer share."""
    import json
    import os

    from deepspeed_tpu.models.hf import config_from_hf

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    file = json.load(open(os.path.join(root, "benchmarks", "configs", "kimi-linear-48b-a3b.json")))
    row = {k: v for k, v in file.items() if k not in ("deployment_share",)}
    row.update(file["published"])
    row["linear_attn_config"] = {**file["linear_attn_config"], **file["published"]["linear_attn_config"]}
    c = config_from_hf(row)
    assert c.n_layers == 27 and c.layer_kinds.count("kda") == 20 and c.layer_kinds.count("full") == 7
    assert [i + 1 for i, k in enumerate(c.layer_kinds) if k == "full"] == [4, 8, 12, 16, 20, 24, 27]
    assert c.moe_dense_lead == 1 and (c.n_experts, c.moe_experts_total, c.moe_top_k) == (256, 0, 8)
    assert c.moe_score == "sigmoid" and c.router_has_bias and c.moe_n_group == 1
    assert c.moe_shared_expert_dim == 1024 and not c.moe_shared_gated and c.moe_routed_scale == 2.446
    assert c.latent and c.q_lora_rank == 0 and c.position == "none" and c.kv_layers == 7
    assert (c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim, c.head_dim) == (512, 128, 64, 128, 192)
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv_kernel) == (32, 128, 4) and c.recurrent_kind == "kda"
    assert c.vocab_size == 163840 and c.ffn_dim == 9216 and c.expert_dim == 1024 and not c.tie_embeddings
    share = config_from_hf(file)
    assert share.layer_kinds == ("kda", "kda", "kda", "full") * 3 and share.kv_layers == 3
    assert (share.n_experts, share.moe_experts_total, share.moe_expert_shard) == (32, 256, 0)
    assert share.router_width == 256 and share.vocab_size == 20480 and share.moe_dense_lead == 1


@pytest.mark.parametrize("change,match", [
    ({"linear_attn_config": {**KIMI_TINY["linear_attn_config"], "kda_layers": [1, 3]}}, "do not partition"),
    ({"linear_attn_config": {**KIMI_TINY["linear_attn_config"], "full_attn_layers": [2, 4, 5]}}, "do not partition"),
    ({"num_nextn_predict_layers": 1}, "multi-token-prediction"),
    ({"num_expert_group": 2}, "num_expert_group > 1"),
    ({"q_lora_rank": 16}, "one query projection and no rotary"),
    ({"mla_use_nope": False}, "one query projection and no rotary"),
    ({"first_k_dense_replace": 0}, "dense lead layers followed by expert layers"),
    ({"moe_router_activation_func": "softmax"}, "expected 'sigmoid'"),
    ({"num_experts": 4}, "is not one chip's share"),
])
def test_kimi_linear_refuses_what_it_cannot_map(change, match):
    from deepspeed_tpu.models.hf import config_from_hf

    with pytest.raises(ValueError, match=match):
        config_from_hf({**KIMI_TINY, **change})


def _latent_hybrid(**change):
    from deepspeed_tpu.models.transformer import TransformerConfig

    base = dict(
        vocab_size=64, hidden_size=32, n_layers=4, n_heads=2, head_dim_override=24, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, position="none",
        layer_kinds=("kda", "full", "kda", "full"), kda_heads=2, kda_head_dim=16,
        n_experts=2, moe_top_k=1, moe_dense_lead=1, moe_drop_tokens=False)
    return TransformerConfig(**{**base, **change})


@pytest.mark.parametrize("change,match", [
    # latent attention as the "full" kind of a hybrid stack: lifted; a layer of
    # two sub-blocks in such a stack is still refused
    ({"moe_shortcut": True, "moe_dense_lead": 0}, "nor\\s+as layer kinds with a layer of two sub-blocks"),
    # one query projection (q_lora_rank 0): lifted; a negative rank is still refused
    ({"q_lora_rank": -1}, "q_lora_rank 0: one query projection"),
    # no positions: lifted; learned positions are still refused
    ({"position": "learned"}, "rotary or no positions"),
    # a dense lead layer in a hybrid stack: lifted; as many lead layers as layers is still refused
    ({"moe_dense_lead": 4}, "some, not all"),
    ({"moe_dense_lead": 1, "n_experts": 0}, "some, not all"),
    # a third recurrent kind: one kind a stack still
    ({"layer_kinds": ("kda", "full", "gdn", "full")}, "ONE of 'gdn', 'mamba' and 'kda'"),
    ({"kda_heads": 0}, "needs kda_heads / kda_head_dim"),
])
def test_the_lifted_refusals_still_refuse_what_does_not_compose(change, match):
    assert _latent_hybrid().latent and _latent_hybrid().hybrid   # what is lifted builds
    assert _latent_hybrid(q_lora_rank=8, position="rope").recurrent_kind == "kda"
    with pytest.raises(ValueError, match=match):
        _latent_hybrid(**change)


def test_kimi_linear_checkpoint_names_round_trip(tmp_path):
    """A checkpoint written under the names ``_kimi_linear_layer`` reads (all 6
    experts; the three convs and projections of a KDA layer apart) comes back as
    the seeded tree: by kind, the lead layer's MLP and the expert blocks apart,
    this chip's experts (2-3), the router whole. ``forward()`` on it equals the
    benchmark's plain reference."""
    import dataclasses
    import importlib
    import json

    from safetensors.torch import save_file

    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.models.transformer import init_params

    cfg = dataclasses.replace(config_from_hf(KIMI_TINY), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    other = init_params(cfg, jax.random.key(1))["layers"]["sparse"]   # the other chips' experts
    L = params["layers"]
    state = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"].T}
    mlp = (("w_gate", "gate_proj", "w1"), ("w_up", "up_proj", "w3"), ("w_down", "down_proj", "w2"))
    C = cfg.kda_heads * cfg.kda_head_dim
    for i, kind in enumerate(cfg.layer_kinds):
        p, a, k = f"model.layers.{i}", f"model.layers.{i}.self_attn", cfg.layer_kinds[:i].count(kind)
        state[f"{p}.input_layernorm.weight"] = L["attn_norm"][i]
        state[f"{p}.post_attention_layernorm.weight"] = L["mlp_norm"][i]
        if kind == "full":
            for name, hf in (("wq", "q_proj"), ("wkv_a", "kv_a_proj_with_mqa"), ("wkv_b", "kv_b_proj"),
                             ("wo", "o_proj")):
                state[f"{a}.{hf}.weight"] = L["full"][name][k].T
            state[f"{a}.kv_a_layernorm.weight"] = L["full"]["kv_a_norm"][k]
        else:
            kda = {n: w[k] for n, w in L["kda"].items()}
            for j, n in enumerate("qkv"):
                state[f"{a}.{n}_proj.weight"] = kda["kda_qkv"][:, j * C: (j + 1) * C].T
                state[f"{a}.{n}_conv1d.weight"] = kda["kda_conv"][:, j * C: (j + 1) * C].T[:, None, :]
            state[f"{a}.A_log"] = kda["kda_a_log"].reshape(1, 1, -1, 1)
            state[f"{a}.dt_bias"] = kda["kda_dt_bias"]
            for name, hf in (("kda_f_a", "f_a_proj"), ("kda_f_b", "f_b_proj"), ("kda_b", "b_proj"),
                             ("kda_g_a", "g_a_proj"), ("kda_g_b", "g_b_proj"), ("kda_out", "o_proj")):
                state[f"{a}.{hf}.weight"] = kda[name].T
            state[f"{a}.o_norm.weight"] = kda["kda_norm"]
        if i == 0:
            for name, hf, _ in mlp:
                state[f"{p}.mlp.{hf}.weight"] = L["lead"][name][0].T
            continue
        m, s = f"{p}.block_sparse_moe", i - 1
        state[f"{m}.gate.weight"] = L["sparse"]["router"][s].T
        state[f"{m}.gate.e_score_correction_bias"] = L["sparse"]["router_bias"][s]
        for name, hf, w in mlp:
            state[f"{m}.shared_experts.{hf}.weight"] = L["sparse"][f"shared_{name[2:]}"][s].T
            for e in range(6):
                mine = L["sparse"][name][s][e - 2] if 2 <= e < 4 else other[name][s][e % 2]
                state[f"{m}.experts.{e}.{w}.weight"] = mine.T
    save_file({k: torch.tensor(np.ascontiguousarray(np.asarray(v, np.float32)))
               for k, v in state.items()}, str(tmp_path / "model.safetensors"))
    json.dump(KIMI_TINY, open(tmp_path / "config.json", "w"))
    got_cfg, got = load_hf_model(str(tmp_path), dtype="float32")
    assert got_cfg == cfg and got_cfg.moe_expert_shard == 1
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert set(flat_got) == {k for k, _ in flat_want}
    for k, v in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[k]), np.asarray(v), err_msg=str(k))
    ref = importlib.import_module("benchmarks.reference.kimi_linear")
    tokens = np.random.default_rng(3).integers(0, 256, size=(1, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ours, _ = jax.jit(lambda p, t: forward(p, t, got_cfg))(got, jnp.asarray(tokens))
        np.testing.assert_allclose(np.asarray(ours[0]), np.asarray(ref.logits(got, tokens[0], KIMI_TINY)),
                                   atol=2e-5, rtol=0)
