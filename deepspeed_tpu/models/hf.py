"""HF checkpoint import: per-architecture loaders → the native model family.

Analogue of the reference checkpoint-shard loading + per-arch containers
(``module_inject/load_checkpoint.py``, ``module_inject/containers/``,
``inference/v2/model_implementations/{llama_v2,mistral,mixtral,qwen_v2,
qwen_v2_moe,falcon,phi,phi3}``): a HF causal-LM checkpoint directory becomes
a (:class:`TransformerConfig`, stacked-params pytree) pair that trains or
serves through ``deepspeed_tpu.initialize`` / ``init_inference`` unchanged.

Supported ``model_type``s: llama, mistral, qwen2, qwen2_moe, qwen3, qwen3_next
(Gated DeltaNet and gated-attention layers, a share of the experts), jamba
(Mamba-1 layers with normed dt / B / C beside attention layers without
positions; the dense form, ``num_experts`` 1),
exaone_moe (K-EXAONE: window and full attention layers, output-normed blocks,
a dense lead layer then sigmoid-routed experts), axk1 (A.X-K1: DeepseekV3's
latent attention, one low-rank vector a token in place of per-head keys and
values, YaRN rotary on 64 shared dims; a dense lead layer then sigmoid-routed
experts chosen inside the best groups, a share of them), mimo_v2_flash
(MiMo-V2-Flash: window layers of 8 KV heads with a learned sink beside full
layers of 4, keys of 192 against values of 128, a rotary base by layer kind, a
dense lead layer then sigmoid-routed experts with no shared one), longcat_flash
(LongCat-Flash: a layer of two latent-attention sub-blocks with dense MLPs and
one expert block on a shortcut across them, a softmax router whose last ids
are identity experts), kimi_linear (Kimi Linear: Kimi Delta Attention layers, the
delta rule with a decay a key channel, beside latent-attention layers with one
query projection and no positions; a dense lead layer then sigmoid-routed
experts with a selection bias and a shared one), qwen3_moe (per-head q/k
RMSNorm), mixtral, olmoe (q/k RMSNorm over the whole projection width; the four MoE types import drop-free: ``moe_drop_tokens``
false), falcon, phi (incl. qk_layernorm),
phi3, gpt2, gpt_neo, opt, gemma, bloom, gptj, gpt_neox, internlm, stablelm
(incl. qk_layernorm), starcoder2, megatron_gpt (Megatron-LM GPT state-dict
naming, per-head-interleaved fused qkv), plus the bert/distilbert encoder
family (post-LN bidirectional stack + masked-LM head) and clip_text_model
(the stable-diffusion text tower; unet/vae are N/A here — diffusers is not
in the image) (scaled-RoPE checkpoints —
llama3/yarn/longrope/linear/dynamic — import via ``rope_scaling``;
sliding-window checkpoints — mistral/starcoder2/gpt_neo local — import via
``sliding_window``/``attn_layer_pattern``). Dispatch is by ``config.json``'s
``model_type`` (see
:data:`ARCH_LOADERS`); the inference engine factory additionally dispatches
on ``architectures[0]`` (engine_factory.py).

Weight-layout notes (why each mapping is what it is):
  * HF Linear stores ``[out, in]``; this model family uses JAX's ``[in,
    out]`` → transpose every projection.
  * Layers here are STACKED along a leading ``[n_layers, ...]`` dim (the
    ``lax.scan`` layout), so per-layer tensors stack after transposing.
  * RoPE: HF's ``rotate_half`` IS the half-split convention used by
    ``transformer._rope`` — weights map 1:1, no permutation needed. Phi's
    partial rotary maps to ``rope_frac``.
  * Falcon fuses q/k/v into ``query_key_value`` with a per-kv-group
    interleave under ``new_decoder_architecture`` — de-interleaved here.
  * ``torch`` is only used to read the checkpoint on host (CPU); arrays
    convert to numpy before entering JAX.
"""

import dataclasses
import json
import math
import os
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.utils.logging import logger


def _to_np(t) -> np.ndarray:
    if hasattr(t, "detach"):
        t = t.detach()
    if hasattr(t, "float") and str(getattr(t, "dtype", "")).startswith("torch.bfloat16"):
        t = t.float()
    return np.asarray(t.cpu() if hasattr(t, "cpu") else t)


def _np_cast(a, dtype: str) -> np.ndarray:
    """Host-only dtype cast (ml_dtypes carries bf16 in numpy — no device
    round-trip for multi-GB checkpoints)."""
    import ml_dtypes

    a = _to_np(a)
    if a.dtype == np.dtype("V2") or str(a.dtype) == "bfloat16":
        a = a.view(ml_dtypes.bfloat16).astype(np.float32)
    target = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32, "float16": np.float16}[dtype]
    return a.astype(target)


def dataclass_replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


def _load_state_dict(path: str) -> Dict[str, Any]:
    """Read all weights of a HF checkpoint dir (safetensors preferred,
    sharded or single-file; torch .bin fallback)."""
    index = os.path.join(path, "model.safetensors.index.json")
    single_st = os.path.join(path, "model.safetensors")
    torch_bin = os.path.join(path, "pytorch_model.bin")
    state: Dict[str, Any] = {}
    if os.path.isfile(index) or os.path.isfile(single_st):
        # framework="pt": the numpy backend cannot represent bf16 tensors;
        # torch (cpu) reads them and _to_np upcasts
        from safetensors import safe_open

        files = (
            sorted({os.path.join(path, s) for s in json.load(open(index))["weight_map"].values()})
            if os.path.isfile(index)
            else [single_st]
        )
        for shard in files:
            with safe_open(shard, framework="pt") as f:
                for k in f.keys():
                    state[k] = _to_np(f.get_tensor(k))
    elif os.path.isfile(torch_bin):
        import torch

        state = {k: _to_np(v) for k, v in torch.load(torch_bin, map_location="cpu", weights_only=True).items()}
    else:
        raise FileNotFoundError(f"no safetensors/bin checkpoint under {path}")
    return state


def _getter(hf_cfg) -> Callable:
    return (lambda k, d=None: hf_cfg.get(k, d)) if isinstance(hf_cfg, dict) else (
        lambda k, d=None: getattr(hf_cfg, k, d)
    )


# ---------------------------------------------------------------------------
# per-arch config translation
# ---------------------------------------------------------------------------
def _parse_rope_scaling(get):
    """HF rope_scaling → the canonical hashable config form (llama3 / yarn /
    longrope / linear / dynamic — transformer.rope_params implements the
    math). Unknown types still fail fast: silently building plain-theta RoPE
    would load without error and produce wrong logits."""
    from deepspeed_tpu.models.transformer import rope_scaling_from_hf

    return rope_scaling_from_hf(
        get("rope_scaling", None), get("original_max_position_embeddings", None)
    )


def _llama_like_config(get, **extra) -> TransformerConfig:
    base = dict(
        rope_scaling=_parse_rope_scaling(get),
        vocab_size=get("vocab_size"),
        hidden_size=get("hidden_size"),
        n_layers=get("num_hidden_layers"),
        n_heads=get("num_attention_heads"),
        n_kv_heads=get("num_key_value_heads", None),
        ffn_hidden_size=get("intermediate_size"),
        max_seq_len=get("max_position_embeddings", 2048),
        norm="rmsnorm",
        activation="swiglu",
        position="rope",
        rope_theta=float(get("rope_theta", 10000.0)),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )
    base.update(extra)
    return TransformerConfig(**base)


def _expert_share(get, mt: str, key: str):
    """(experts held, published count, this chip's share index) from the
    configuration's ``key`` and ``deployment_share`` (this repo's key: ``key`` is
    one chip's share of ``deployment_share[key]`` over ``chips_per_layer``)."""
    held = int(get(key))
    share = get("deployment_share", None) or {}
    total = int(share.get(key, held))
    chips = int(share.get("chips_per_layer", 1))
    if total != held * chips:
        raise ValueError(
            f"{mt}: deployment_share says {chips} chips share {total} experts; "
            f"{key}={held} is not one chip's share of them")
    return held, total, int(share.get("share_index", 0))


def _router_groups(get, mt: str):
    """(n_group, topk_group) of a DeepseekV3-style grouped router."""
    n_group, topk_group = int(get("n_group", 1) or 1), int(get("topk_group", 1) or 1)
    if n_group < 1 or not 0 < topk_group <= n_group:
        raise ValueError(f"{mt}: n_group={n_group}, topk_group={topk_group}")
    return n_group, topk_group


def _jamba_config(get) -> TransformerConfig:
    """Jamba (``jamba``): Mamba-1 layers with RMSNorms on dt, B and C, and every
    ``attn_layer_period``-th layer (from ``attn_layer_offset``) grouped softmax
    attention with NO position term (the state-space layers carry the order);
    the layer order is ``JambaConfig.layers_block_type``'s. Every layer's
    feed-forward is a gated MLP: a stack with experts (``num_experts > 1``)
    is refused, since an expert layer every ``expert_layer_period`` among dense
    ones is a third stacking of the MLP's parameters that nothing here has."""
    experts = int(get("num_experts", 1) or 1)
    if experts > 1:
        raise ValueError(
            f"jamba: num_experts={experts}: only the dense form (num_experts 1, every "
            "feed-forward a gated MLP) is supported; expert layers alternating with dense "
            "ones by expert_layer_period have no stacking here")
    n_layers = int(get("num_hidden_layers"))
    period, offset = int(get("attn_layer_period", 8)), int(get("attn_layer_offset", 4))
    kinds = tuple("full" if i % period == offset else "mamba" for i in range(n_layers))
    if "mamba" not in kinds or "full" not in kinds:
        raise ValueError(
            f"jamba: attn_layer_period={period}, attn_layer_offset={offset} over {n_layers} "
            "layers leaves one kind of layer alone; a stack needs both")
    if not bool(get("mamba_conv_bias", True)) or bool(get("mamba_proj_bias", False)):
        raise ValueError(
            "jamba: mamba_conv_bias false or mamba_proj_bias true: only the published form "
            "(a conv with bias, projections without) is supported")
    hidden = int(get("hidden_size"))
    rank = get("mamba_dt_rank", "auto")
    return _llama_like_config(
        get,
        position="none",
        layer_kinds=kinds,
        mamba_d_inner=int(get("mamba_expand", 2)) * hidden,
        mamba_d_state=int(get("mamba_d_state", 16)),
        mamba_dt_rank=-(-hidden // 16) if rank == "auto" else int(rank),
        mamba_conv_kernel=int(get("mamba_d_conv", 4)),
        norm_eps=float(get("rms_norm_eps", 1e-6)),
    )


def _axk1_config(get) -> TransformerConfig:
    """A.X-K1 (``axk1``), DeepseekV3's block under its key names: latent
    attention (``q_lora_rank`` / ``kv_lora_rank``, heads of ``qk_nope_head_dim`` +
    ``qk_rope_head_dim`` against values of ``v_head_dim``, rotary on the rope dims
    alone in interleaved pairs, the softmax scale times YaRN's ``mscale``
    squared), ``first_k_dense_replace`` dense lead layers, then experts routed by
    sigmoid score inside the ``topk_group`` best of ``n_group`` groups
    (``topk_method`` "none": no selection bias; "noaux_tc": with one),
    renormalised, scaled, plus ungated shared experts. ``deployment_share`` as
    for qwen3_next (``n_routed_experts`` held of the published count)."""
    import math

    n_layers = int(get("num_hidden_layers"))
    lead = int(get("first_k_dense_replace", 0) or 0)
    if not 0 < lead < n_layers or int(get("moe_layer_freq", 1) or 1) != 1:
        raise ValueError(
            f"axk1: first_k_dense_replace={lead}, moe_layer_freq={get('moe_layer_freq', 1)}: "
            "supported are dense lead layers followed by expert layers, some of each")
    if get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"axk1: scoring_func={get('scoring_func')!r}, expected 'sigmoid'")
    method = get("topk_method", "noaux_tc")
    if method not in ("none", "noaux_tc"):
        raise ValueError(f"axk1: topk_method={method!r}, expected 'none' or 'noaux_tc'")
    if get("attention_bias", False) or not get("q_lora_rank", None):
        raise ValueError("axk1: attention_bias, or no q_lora_rank, is not supported")
    held, total, shard = _expert_share(get, "axk1", "n_routed_experts")
    n_group, topk_group = _router_groups(get, "axk1")
    dn, dr = int(get("qk_nope_head_dim")), int(get("qk_rope_head_dim"))
    # DeepseekV3Attention: the scale of the whole head, times mscale^2 where
    # the rotary is YaRN-scaled with an mscale_all_dim
    scale = (dn + dr) ** -0.5
    scaling = get("rope_scaling", None) or {}
    if scaling.get("mscale_all_dim") and float(scaling.get("factor", 1.0)) > 1:
        m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(float(scaling["factor"])) + 1.0
        scale *= m * m
    expert_dim = int(get("moe_intermediate_size"))
    return _llama_like_config(
        get,
        head_dim_override=dn + dr,
        attn_scale=scale,
        kv_lora_rank=int(get("kv_lora_rank")),
        q_lora_rank=int(get("q_lora_rank")),
        qk_nope_dim=dn,
        qk_rope_dim=dr,
        v_head_dim=int(get("v_head_dim")),
        rope_interleave=bool(get("rope_interleave", True)),
        n_experts=held,
        moe_experts_total=total if total != held else 0,
        moe_expert_shard=shard,
        moe_top_k=get("num_experts_per_tok"),
        moe_norm_topk_prob=bool(get("norm_topk_prob", True)),
        moe_drop_tokens=False,  # the published block never drops a token
        moe_dense_lead=lead,
        moe_expert_dim=expert_dim,
        moe_score="sigmoid",
        moe_router_bias=method == "noaux_tc",
        moe_n_group=n_group,
        moe_topk_group=topk_group,
        moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_expert_dim=expert_dim * int(get("n_shared_experts", 0) or 0),
        moe_shared_gated=False,
    )


def _kimi_linear_config(get) -> TransformerConfig:
    """Kimi Linear (``kimi_linear``): ``linear_attn_config`` lists, numbered
    from 1, the layers that are Kimi Delta Attention (``kda_layers``: the delta
    rule with a decay a key channel, ``num_heads`` heads of ``head_dim``, a conv
    of ``short_conv_kernel_size`` taps) and those that are latent attention
    (``full_attn_layers``: DeepseekV3's, with ONE query projection where
    ``q_lora_rank`` is null and NO rotary where ``mla_use_nope``);
    ``first_k_dense_replace`` dense lead layers, then ``num_experts`` experts
    routed by sigmoid score + a selection bias in one group, renormalised,
    scaled, plus ungated shared experts. ``deployment_share`` as for qwen3_next
    (``num_experts`` held of the published count)."""
    n_layers = int(get("num_hidden_layers"))
    lin = get("linear_attn_config", None) or {}
    kda, full = (sorted(int(i) for i in lin.get(k, ())) for k in ("kda_layers", "full_attn_layers"))
    if sorted(kda + full) != list(range(1, n_layers + 1)) or not kda or not full:
        raise ValueError(
            f"kimi_linear: linear_attn_config.kda_layers={kda} and full_attn_layers={full} do "
            f"not partition layers 1..{n_layers} into some of each")
    lead = int(get("first_k_dense_replace", 0) or 0)
    if not 0 < lead < n_layers or int(get("moe_layer_freq", 1) or 1) != 1:
        raise ValueError(
            f"kimi_linear: first_k_dense_replace={lead}, moe_layer_freq={get('moe_layer_freq', 1)}: "
            "supported are dense lead layers followed by expert layers, some of each")
    if get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError(f"kimi_linear: moe_router_activation_func="
                         f"{get('moe_router_activation_func')!r}, expected 'sigmoid'")
    if int(get("num_nextn_predict_layers", 0) or 0) > 0:
        raise ValueError(
            "kimi_linear: num_nextn_predict_layers > 0: a multi-token-prediction layer is a "
            "second head on the stream, and nothing here runs or loads one")
    if int(get("num_expert_group", 1) or 1) > 1:
        raise ValueError(
            "kimi_linear: num_expert_group > 1: how KimiMoEGate scores a group (its largest "
            "score, or its two largest with the bias) could not be read here; one group is "
            "what the published model has")
    if get("q_lora_rank", None) or not bool(get("mla_use_nope", False)):
        raise ValueError(
            "kimi_linear: a q_lora_rank, or mla_use_nope false: supported is the published "
            "form, one query projection and no rotary in the latent layers")
    held, total, shard = _expert_share(get, "kimi_linear", "num_experts")
    dn, dr = int(get("qk_nope_head_dim")), int(get("qk_rope_head_dim"))
    expert_dim = int(get("moe_intermediate_size"))
    return _llama_like_config(
        get,
        position="none",
        max_seq_len=int(get("model_max_length", None) or get("max_position_embeddings", 2048)),
        layer_kinds=tuple("kda" if i + 1 in kda else "full" for i in range(n_layers)),
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        kda_conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
        head_dim_override=dn + dr,
        kv_lora_rank=int(get("kv_lora_rank")),
        qk_nope_dim=dn,
        qk_rope_dim=dr,
        v_head_dim=int(get("v_head_dim")),
        n_experts=held,
        moe_experts_total=total if total != held else 0,
        moe_expert_shard=shard,
        moe_top_k=get("num_experts_per_token"),
        moe_norm_topk_prob=bool(get("moe_renormalize", True)),
        moe_drop_tokens=False,  # the published block never drops a token
        moe_dense_lead=lead,
        moe_expert_dim=expert_dim,
        moe_score="sigmoid",
        moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_expert_dim=expert_dim * int(get("num_shared_experts", 0) or 0),
        moe_shared_gated=False,
    )


def _longcat_flash_config(get) -> TransformerConfig:
    """LongCat-Flash (``longcat_flash``) under its own key names: ``num_layers``
    layers of TWO sub-blocks each (latent attention as DeepseekV3's, with
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: queries times sqrt(hidden /
    q_lora_rank), the normed latent times sqrt(hidden / kv_lora_rank); a dense
    SwiGLU of ``ffn_hidden_size``) and ONE expert block on a shortcut across them
    (``moe_shortcut``): a softmax router over ``n_routed_experts`` +
    ``zero_expert_num`` ids, ``moe_topk`` CHOSEN on probability +
    ``e_score_correction_bias`` and weighted by the probability alone, not
    renormalised, times ``routed_scaling_factor``; experts of
    ``expert_ffn_hidden_size``, the ids behind them the identity. No shared
    expert, no dense lead layer, an untied head. ``deployment_share`` as for
    qwen3_next (``n_routed_experts`` held of the published count; the identity
    experts are every chip's)."""
    import math

    mt = "longcat_flash"
    if get("attention_method", "MLA") != "MLA" or get("attention_bias", False) or not get(
            "q_lora_rank", None):
        raise ValueError(f"{mt}: attention_method MLA with a q_lora_rank and no bias is supported")
    zero = int(get("zero_expert_num", 0) or 0)
    if zero and get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"{mt}: zero_expert_type={get('zero_expert_type')!r}, expected 'identity'")
    held, total, shard = _expert_share(get, mt, "n_routed_experts")
    h = int(get("hidden_size"))
    dn, dr = int(get("qk_nope_head_dim")), int(get("qk_rope_head_dim"))
    qr, rank = int(get("q_lora_rank")), int(get("kv_lora_rank"))
    return _llama_like_config(
        get,
        n_layers=int(get("num_layers")),
        ffn_hidden_size=get("ffn_hidden_size"),
        head_dim_override=dn + dr,
        kv_lora_rank=rank,
        q_lora_rank=qr,
        qk_nope_dim=dn,
        qk_rope_dim=dr,
        v_head_dim=int(get("v_head_dim")),
        rope_interleave=bool(get("rope_interleave", True)),
        latent_norm_eps=1e-6,  # LongcatFlashMLA's inner norms: its RMSNorm's default, not rms_norm_eps
        latent_q_scale=math.sqrt(h / qr) if get("mla_scale_q_lora", False) else 1.0,
        latent_kv_scale=math.sqrt(h / rank) if get("mla_scale_kv_lora", False) else 1.0,
        moe_shortcut=True,
        n_experts=held,
        moe_experts_total=total if total != held else 0,
        moe_expert_shard=shard,
        moe_zero_experts=zero,
        moe_top_k=int(get("moe_topk")),
        moe_norm_topk_prob=bool(get("norm_topk_prob", False)),
        moe_drop_tokens=False,  # the published block never drops a token
        moe_expert_dim=int(get("expert_ffn_hidden_size")),
        moe_score="softmax",
        moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
    )


def _exaone_moe_config(get) -> TransformerConfig:
    """K-EXAONE (``exaone_moe``): window and full attention layers in one stack
    (``layer_types``, window ``sliding_window``), a dense lead MLP then expert
    layers (``mlp_layer_types``), the expert block DeepseekV3MoE's: a sigmoid
    router with a selection bias, top-k renormalised and scaled, ungated shared
    experts. What ``config.json`` has no key for is the family's published
    hybrid model's (transformers ``Exaone4DecoderLayer`` / ``Exaone4Attention``):
    RMSNorm on each block's OUTPUT, per-head q/k norm, rotary on the window
    layers only. Under a pipeline stage ``num_hidden_layers`` is below the
    lists' length and the stage is their head; ``deployment_share`` as for
    qwen3_next. The multi-token-prediction layer is no part of the model's own
    logits and is left out."""
    n_layers = int(get("num_hidden_layers"))
    kinds = list(get("layer_types", None) or [])[:n_layers]
    mlps = list(get("mlp_layer_types", None) or [])[:n_layers]
    lead = int(get("first_k_dense_replace", 0) or 0)
    if not mlps:
        mlps = ["dense"] * lead + ["sparse"] * (n_layers - lead)
    if (len(kinds) != n_layers or set(kinds) - {"sliding_attention", "full_attention"}):
        raise ValueError(f"exaone_moe: layer_types={kinds!r} for {n_layers} layers")
    lead = mlps.index("sparse") if "sparse" in mlps else n_layers
    if (len(mlps) != n_layers or not 0 < lead < n_layers
            or mlps != ["dense"] * lead + ["sparse"] * (n_layers - lead)):
        raise ValueError(
            f"exaone_moe: mlp_layer_types={mlps!r}: supported are dense lead layers "
            "followed by expert layers, some of each")
    n_group, topk_group = _router_groups(get, "exaone_moe")
    if get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"exaone_moe: scoring_func={get('scoring_func')!r}, expected 'sigmoid'")
    window = int(get("sliding_window", 0) or 0)
    local = tuple(int(k == "sliding_attention") for k in kinds)
    if any(local) and window <= 0:
        raise ValueError("exaone_moe: sliding_attention layers without a sliding_window")
    if not any(local):
        raise ValueError("exaone_moe: no sliding_attention layer: rotary sits on those alone, "
                         "and a stack of none has no position term at all")
    held, total, shard = _expert_share(get, "exaone_moe", "num_experts")
    rope = get("rope_parameters", None) or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"exaone_moe: rope_type={rope.get('rope_type')!r} is not supported")
    expert_dim = int(get("moe_intermediate_size"))
    return _llama_like_config(
        get,
        rope_theta=float(rope.get("rope_theta", get("rope_theta", 10000.0))),
        norm_scheme="out",
        qk_norm=True,
        head_dim_override=int(get("head_dim")),
        sliding_window=window,
        attn_layer_pattern=local,
        rope_window_only=True,
        n_experts=held,
        moe_experts_total=total if total != held else 0,
        moe_expert_shard=shard,
        moe_top_k=get("num_experts_per_tok"),
        moe_norm_topk_prob=bool(get("norm_topk_prob", True)),
        moe_drop_tokens=False,  # the published block never drops a token
        moe_dense_lead=lead,
        moe_expert_dim=expert_dim,
        moe_score="sigmoid",
        # (its router has a selection bias: a group scores its top two summed)
        moe_n_group=n_group,
        moe_topk_group=topk_group,
        moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_expert_dim=expert_dim * int(get("num_shared_experts", 0) or 0),
        moe_shared_gated=False,
    )


def _mimo_v2_flash_config(get) -> TransformerConfig:
    """MiMo-V2-Flash (``mimo_v2_flash``): window and full attention layers in
    one stack (``hybrid_layer_pattern``: 1 window, 0 full; window
    ``sliding_window``) that differ in more than the window: the window layers
    have ``swa_num_key_value_heads`` KV heads and a learned sink a head in the
    softmax's denominator (``add_swa_attention_sink_bias``), the full ones
    ``num_key_value_heads`` and none; rotary turns the first ``int(head_dim x
    partial_rotary_factor)`` dims (half-split) at base ``swa_rope_theta`` on
    window layers and ``rope_theta`` on full ones; a key is ``head_dim`` wide, a
    value ``v_head_dim``, scaled by ``attention_value_scale``. ``moe_layer_freq``
    (0 dense, 1 experts) must be dense lead layers then expert layers
    (``first_k_dense_replace``, this repo's derived key, may say the count); the
    expert block is DeepseekV3MoE's with no shared expert: a sigmoid router,
    chosen on score + ``e_score_correction_bias`` (``noaux_tc``), renormalised,
    times ``routed_scaling_factor`` (null: 1). Pre-norm blocks, no q/k norm.
    Under a pipeline stage ``num_hidden_layers`` is below the lists' length and
    the stage is their head; ``deployment_share`` as for qwen3_next. The
    multi-token-prediction layers are no part of the model's own logits."""
    mt = "mimo_v2_flash"
    n_layers = int(get("num_hidden_layers"))
    local = tuple(int(f) for f in list(get("hybrid_layer_pattern", None) or [])[:n_layers])
    freq = [int(f) for f in list(get("moe_layer_freq", None) or [])[:n_layers]]
    window = int(get("sliding_window", 0) or get("sliding_window_size", 0) or 0)
    if len(local) != n_layers or set(local) - {0, 1} or not 0 < sum(local) < n_layers or window <= 0:
        raise ValueError(f"{mt}: hybrid_layer_pattern={local!r} for {n_layers} layers, window "
                         f"{window}: expected window (1) and full (0) layers, some of each")
    lead = freq.index(1) if 1 in freq else n_layers
    if len(freq) != n_layers or not 0 < lead < n_layers or freq != [0] * lead + [1] * (n_layers - lead):
        raise ValueError(f"{mt}: moe_layer_freq={freq!r}: supported are dense lead layers "
                         "followed by expert layers, some of each")
    if get("scoring_func", "sigmoid") != "sigmoid" or get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"{mt}: scoring_func={get('scoring_func')!r}, topk_method="
                         f"{get('topk_method')!r}: expected 'sigmoid' and 'noaux_tc'")
    d, dv = int(get("head_dim")), int(get("v_head_dim"))
    same = (("swa_head_dim", d), ("swa_v_head_dim", dv),
            ("swa_num_attention_heads", int(get("num_attention_heads"))))
    if (get("attention_bias", False) or get("add_full_attention_sink_bias", False)
            or get("n_shared_experts", None) or any(int(get(k, v) or v) != v for k, v in same)):
        raise ValueError(f"{mt}: attention_bias, a sink on the full layers, shared experts, or "
                         "window layers whose query heads or head widths differ from the full "
                         "layers', are not supported")
    n_group, topk_group = _router_groups(get, mt)
    held, total, shard = _expert_share(get, mt, "n_routed_experts")
    return _llama_like_config(
        get,
        norm_eps=float(get("layernorm_epsilon", 1e-5)),
        head_dim_override=d,
        v_head_dim=dv if dv != d else 0,
        rope_frac=float(get("partial_rotary_factor", 1.0)),
        sliding_window=window,
        attn_layer_pattern=local,
        window_kv_heads=int(get("swa_num_key_value_heads", None) or get("num_key_value_heads")),
        attn_sink_window=bool(get("add_swa_attention_sink_bias", False)),
        window_rope_theta=float(get("swa_rope_theta", 0.0) or 0.0),
        attn_value_scale=float(get("attention_value_scale", None) or 1.0),
        n_experts=held,
        moe_experts_total=total if total != held else 0,
        moe_expert_shard=shard,
        moe_top_k=get("num_experts_per_tok"),
        moe_norm_topk_prob=bool(get("norm_topk_prob", True)),
        moe_drop_tokens=False,  # the published block never drops a token
        moe_dense_lead=lead,
        moe_expert_dim=int(get("moe_intermediate_size")),
        moe_score="sigmoid",
        moe_n_group=n_group,
        moe_topk_group=topk_group,
        moe_routed_scale=float(get("routed_scaling_factor", None) or 1.0),
    )


def config_from_hf(hf_cfg) -> TransformerConfig:
    """HF config (object or dict) → TransformerConfig; dispatches on
    ``model_type`` (llama when absent)."""
    get = _getter(hf_cfg)
    mt = get("model_type", "llama")
    if mt in ("llama", "mistral"):
        head_dim = get("head_dim", None)
        derived = get("hidden_size") // get("num_attention_heads")
        override = int(head_dim) if head_dim is not None and int(head_dim) != derived else None
        bias = bool(get("attention_bias", False))
        # mistral sliding_window (None on v0.2+ checkpoints → full attention)
        window = int(get("sliding_window", None) or 0) if mt == "mistral" else 0
        if window >= get("max_position_embeddings", 2048):
            window = 0  # window beyond the position range is full attention
        return _llama_like_config(
            get, head_dim_override=override, attn_qkv_bias=bias,
            attn_out_bias=bias, sliding_window=window,
        )
    if mt == "internlm":
        # InternLM is llama + biased attention projections (reference
        # module_inject/containers/internlm.py). `bias` covers q/k/v AND o
        # (HF InternLM passes one flag to all four Linears). internlm2's
        # fused-wqkv export is not supported.
        bias = bool(get("bias", True))
        return _llama_like_config(get, attn_qkv_bias=bias, attn_out_bias=bias)
    if mt == "qwen2":
        return _llama_like_config(get, attn_qkv_bias=True)
    if mt == "qwen3":
        # qwen2 minus qkv bias, plus per-head q/k RMSNorm and a decoupled
        # head_dim (always 128 regardless of hidden/heads)
        head_dim = get("head_dim", None)
        derived = get("hidden_size") // get("num_attention_heads")
        return _llama_like_config(
            get,
            qk_norm=True,
            head_dim_override=int(head_dim) if head_dim is not None and int(head_dim) != derived else None,
        )
    if mt == "qwen3_moe":
        sparse_step = get("decoder_sparse_step", 1)
        mlp_only = get("mlp_only_layers", []) or []
        if sparse_step != 1 or mlp_only:
            raise ValueError(
                f"qwen3_moe: decoder_sparse_step={sparse_step}, mlp_only_layers="
                f"{mlp_only} — only uniform MoE stacks are supported"
            )
        head_dim = get("head_dim", None)
        derived = get("hidden_size") // get("num_attention_heads")
        return _llama_like_config(
            get,
            qk_norm=True,
            head_dim_override=int(head_dim) if head_dim is not None and int(head_dim) != derived else None,
            ffn_hidden_size=get("moe_intermediate_size"),
            n_experts=get("num_experts"),
            moe_top_k=get("num_experts_per_tok"),
            moe_norm_topk_prob=bool(get("norm_topk_prob", True)),
            moe_drop_tokens=False,  # HF never drops a token
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.001)),
        )
    if mt == "qwen3_next":
        # three Gated DeltaNet layers to one gated softmax-attention layer
        # (layer i is full attention where (i + 1) % full_attention_interval
        # == 0), every layer with 512 routed experts, top 10 renormalised, and
        # a sigmoid-gated shared expert; every norm but the DeltaNet's gated
        # one is rms(x) * (1 + w). ``deployment_share`` (this repo's key, not
        # Hugging Face's) says that num_experts is one chip's share of
        # deployment_share.num_experts, and which.
        sparse_step = get("decoder_sparse_step", 1)
        mlp_only = get("mlp_only_layers", []) or []
        if sparse_step != 1 or mlp_only:
            raise ValueError(
                f"qwen3_next: decoder_sparse_step={sparse_step}, mlp_only_layers="
                f"{mlp_only}: only stacks with the expert block in every layer are supported"
            )
        n_layers = int(get("num_hidden_layers"))
        every = int(get("full_attention_interval", 4))
        kinds = get("layer_types", None) or [
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(n_layers)]
        unknown = set(kinds) - {"full_attention", "linear_attention"}
        if unknown or len(kinds) != n_layers:
            raise ValueError(f"qwen3_next: layer_types={kinds!r} for {n_layers} layers")
        held = int(get("num_experts"))
        share = get("deployment_share", None) or {}
        total = int(share.get("num_experts", held))
        chips = int(share.get("chips_per_layer", 1))
        if total != held * chips:
            raise ValueError(
                f"qwen3_next: deployment_share says {chips} chips share {total} experts; "
                f"num_experts={held} is not one chip's share of them")
        return _llama_like_config(
            get,
            norm="rmsnorm_1p",
            qk_norm=True,
            head_dim_override=int(get("head_dim")),
            rope_frac=float(get("partial_rotary_factor", 1.0)),
            attn_out_gate=True,
            layer_kinds=tuple("full" if k == "full_attention" else "gdn" for k in kinds),
            gdn_key_heads=int(get("linear_num_key_heads")),
            gdn_value_heads=int(get("linear_num_value_heads")),
            gdn_key_dim=int(get("linear_key_head_dim")),
            gdn_value_dim=int(get("linear_value_head_dim")),
            gdn_conv_kernel=int(get("linear_conv_kernel_dim", 4)),
            ffn_hidden_size=get("moe_intermediate_size"),
            n_experts=held,
            moe_experts_total=total if total != held else 0,
            moe_expert_shard=int(share.get("share_index", 0)),
            moe_top_k=get("num_experts_per_tok"),
            moe_norm_topk_prob=bool(get("norm_topk_prob", True)),
            moe_drop_tokens=False,  # HF never drops a token
            moe_shared_expert_dim=get("shared_expert_intermediate_size", 0) or 0,
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.001)),
        )
    if mt == "jamba":
        return _jamba_config(get)
    if mt == "exaone_moe":
        return _exaone_moe_config(get)
    if mt == "axk1":
        return _axk1_config(get)
    if mt == "kimi_linear":
        return _kimi_linear_config(get)
    if mt == "mimo_v2_flash":
        return _mimo_v2_flash_config(get)
    if mt == "longcat_flash":
        return _longcat_flash_config(get)
    if mt == "qwen2_moe":
        sparse_step = get("decoder_sparse_step", 1)
        mlp_only = get("mlp_only_layers", []) or []
        if sparse_step != 1 or mlp_only:
            # the scan layout wants uniform layers; mixed dense/MoE stacks
            # would need a per-layer dispatch — fail with the real reason
            raise ValueError(
                f"qwen2_moe: decoder_sparse_step={sparse_step}, mlp_only_layers="
                f"{mlp_only} — only uniform MoE stacks are supported"
            )
        return _llama_like_config(
            get,
            attn_qkv_bias=True,
            ffn_hidden_size=get("moe_intermediate_size"),
            n_experts=get("num_experts"),
            moe_top_k=get("num_experts_per_tok"),
            moe_norm_topk_prob=bool(get("norm_topk_prob", False)),
            moe_drop_tokens=False,  # HF never drops a token
            moe_shared_expert_dim=get("shared_expert_intermediate_size", 0) or 0,
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.001)),
        )
    if mt == "mixtral":
        return _llama_like_config(
            get,
            ffn_hidden_size=get("intermediate_size"),
            n_experts=get("num_local_experts"),
            moe_top_k=get("num_experts_per_tok"),
            # HF mixtral ALWAYS renormalizes the top-k routing weights
            moe_norm_topk_prob=True,
            moe_drop_tokens=False,  # HF never drops a token
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.001)),
        )
    if mt == "olmoe":
        # llama attention with an RMSNorm over the WHOLE q and k projection
        # (OlmoeAttention: q_norm(q_proj(x)), before the head reshape), every
        # layer a sparse block of num_experts experts of width
        # intermediate_size, top-k of the float32 softmax, renormalised only
        # where norm_topk_prob says so (the published checkpoints: false)
        if get("clip_qkv", None) is not None:
            raise ValueError(
                f"olmoe: clip_qkv={get('clip_qkv')} is not supported: the q/k/v "
                "projections would have to be clamped before the norm, and no "
                "published checkpoint sets it (OLMoE-1B-7B-0125: null)"
            )
        return _llama_like_config(
            get,
            qk_norm=True,
            qk_norm_kind="rmsnorm_full",
            attn_qkv_bias=bool(get("attention_bias", False)),
            attn_out_bias=bool(get("attention_bias", False)),
            n_experts=get("num_experts"),
            moe_top_k=get("num_experts_per_tok"),
            moe_norm_topk_prob=bool(get("norm_topk_prob", False)),
            moe_drop_tokens=False,  # HF never drops a token
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.01)),
        )
    if mt == "stablelm":
        return TransformerConfig(
            qk_norm=bool(get("qk_layernorm", False)),
            qk_norm_kind="layernorm_per_head",
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            n_kv_heads=get("num_key_value_heads", None),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 4096),
            norm="layernorm",
            activation="swiglu",  # silu-gated MLP under LayerNorm
            position="rope",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_scaling=_parse_rope_scaling(get),
            rope_frac=float(get("partial_rotary_factor", 0.25)),
            norm_eps=float(get("layer_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            attn_qkv_bias=bool(get("use_qkv_bias", False)),
            # parallel residual shares input_layernorm across both branches
            parallel_block=bool(get("use_parallel_residual", False)),
        )
    if mt == "starcoder2":
        act = get("hidden_act", "gelu_pytorch_tanh")
        act_map = {"gelu_pytorch_tanh": "gelu", "gelu_new": "gelu", "gelu": "gelu_exact"}
        if act not in act_map:
            raise ValueError(f"starcoder2: hidden_act={act!r} is not supported")
        bias = bool(get("use_bias", True))
        max_seq = get("max_position_embeddings", 4096)
        # released starcoder2 sets sliding_window=4096 with a 16k position
        # range — native banded masking (sliding_window) keeps the full range
        window = int(get("sliding_window", None) or 0)
        if window >= max_seq:
            window = 0
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            n_kv_heads=get("num_key_value_heads", None),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=max_seq,
            norm="layernorm",
            activation=act_map[act],
            position="rope",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_scaling=_parse_rope_scaling(get),
            norm_eps=float(get("norm_epsilon", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            attn_qkv_bias=bias,
            attn_out_bias=bias,
            mlp_bias=bias,
            sliding_window=window,
        )
    if mt == "gpt_neo":
        h = get("hidden_size")
        act = get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            raise ValueError(f"gpt_neo: activation_function={act!r} is not supported (gelu_new only)")
        n_layers = get("num_layers")
        # expand attention_types ([[types, repeat], ...]) the way
        # GPTNeoConfig.expand_attention_types_params does
        pattern: list = []
        for types, rep in get("attention_types", [[["global"], n_layers]]):
            for _ in range(rep):
                pattern.extend(types)
        if len(pattern) != n_layers:
            raise ValueError(
                f"gpt_neo: attention_types expands to {len(pattern)} layers, "
                f"config has {n_layers}"
            )
        bad = sorted(set(pattern) - {"global", "local"})
        if bad:
            raise ValueError(f"gpt_neo: unknown attention type(s) {bad}")
        any_local = "local" in pattern
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=n_layers,
            n_heads=get("num_heads"),
            ffn_hidden_size=get("intermediate_size", None) or 4 * h,
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm",
            activation="gelu",  # gelu_new = tanh approx
            position="learned",
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=True,
            # q/k/v Linears carry no bias; out_proj and the MLP do
            attn_out_bias=True,
            mlp_bias=True,
            # GPTNeoSelfAttention never scales the logits by 1/sqrt(d)
            attn_scale=1.0,
            sliding_window=int(get("window_size", 256)) if any_local else 0,
            attn_layer_pattern=tuple(int(t == "local") for t in pattern) if any_local else None,
        )
    if mt == "clip_text_model":
        # CLIP's text encoder (reference module_inject/containers/clip.py —
        # the stable-diffusion text tower): causal pre-LN encoder, learned
        # positions, quick_gelu MLP, final LN, NO lm head (use
        # forward_hidden for features; tie_embeddings avoids a head param).
        act_map = {"quick_gelu": "quick_gelu", "gelu": "gelu_exact",
                   "gelu_new": "gelu", "gelu_pytorch_tanh": "gelu"}
        act = get("hidden_act", "quick_gelu")
        if act not in act_map:
            raise ValueError(f"clip_text_model: hidden_act={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 77),
            norm="layernorm",
            activation=act_map[act],
            position="learned",
            norm_eps=float(get("layer_norm_eps", 1e-5)),
            tie_embeddings=True,  # no lm head: features come from forward_hidden
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
        )
    if mt == "bert":
        act_map = {"gelu": "gelu_exact", "gelu_new": "gelu", "gelu_pytorch_tanh": "gelu", "relu": "relu"}
        act = get("hidden_act", "gelu")
        if act not in act_map:
            raise ValueError(f"bert: hidden_act={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 512),
            norm="layernorm",
            activation=act_map[act],
            position="learned",
            norm_eps=float(get("layer_norm_eps", 1e-12)),
            tie_embeddings=True,  # cls.predictions.decoder ties to embeddings
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
            attn_causal=False,
            norm_scheme="post",
            embed_norm=True,  # embeddings.LayerNorm after word+pos+type sum
            type_vocab_size=get("type_vocab_size", 2),
            final_norm=False,
            mlm_head=True,
        )
    if mt == "distilbert":
        if get("sinusoidal_pos_embds", False):
            raise ValueError("distilbert: sinusoidal_pos_embds is not supported (learned only)")
        act_map = {"gelu": "gelu_exact", "relu": "relu"}
        act = get("activation", "gelu")
        if act not in act_map:
            raise ValueError(f"distilbert: activation={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("dim"),
            n_layers=get("n_layers"),
            n_heads=get("n_heads"),
            ffn_hidden_size=get("hidden_dim"),
            max_seq_len=get("max_position_embeddings", 512),
            norm="layernorm",
            activation=act_map[act],
            position="learned",
            norm_eps=1e-12,  # hardcoded in HF modeling_distilbert
            tie_embeddings=True,  # vocab_projector ties to embeddings
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
            attn_causal=False,
            norm_scheme="post",
            embed_norm=True,
            final_norm=False,
            mlm_head=True,
        )
    if mt == "megatron_gpt":
        # Megatron-LM GPT checkpoints (reference module_inject/containers/
        # megatron_gpt.py): gpt2-architecture model, megatron state-dict
        # naming with per-head-interleaved fused query_key_value
        h = get("hidden_size") or get("n_embd")
        act = get("activation_function", "gelu_new")
        act_map = {"gelu_new": "gelu", "gelu_pytorch_tanh": "gelu", "gelu": "gelu_exact"}
        if act not in act_map:
            raise ValueError(f"megatron_gpt: activation_function={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=get("num_layers") or get("num_hidden_layers") or get("n_layer"),
            n_heads=get("num_attention_heads") or get("n_head"),
            ffn_hidden_size=get("ffn_hidden_size", None) or 4 * h,
            max_seq_len=get("max_position_embeddings") or get("n_positions") or 1024,
            norm="layernorm",
            activation=act_map[act],
            position="learned",
            norm_eps=float(get("layernorm_epsilon", 1e-5)),
            tie_embeddings=True,  # megatron GPT always ties the output head
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
        )
    if mt == "falcon":
        if get("alibi", False):
            raise ValueError("falcon: alibi position encoding is not supported (rope checkpoints only)")
        nh = get("num_attention_heads")
        if get("new_decoder_architecture", False):
            n_kv = get("num_kv_heads", nh)
        else:
            n_kv = 1 if get("multi_query", True) else nh
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=nh,
            n_kv_heads=n_kv,
            ffn_hidden_size=get("ffn_hidden_size", None) or 4 * get("hidden_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm",
            activation="gelu_exact",  # falcon's MLP is torch nn.GELU (erf)
            position="rope",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_scaling=_parse_rope_scaling(get),
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            parallel_block=bool(get("parallel_attn", True)),
            attn_qkv_bias=bool(get("bias", False)),
            attn_out_bias=bool(get("bias", False)),
            mlp_bias=bool(get("bias", False)),
        )
    if mt == "phi":
        act = get("hidden_act", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            # a 'gelu' (erf) checkpoint would silently load with tanh GELU
            raise ValueError(f"phi: hidden_act={act!r} is not supported (gelu_new only)")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            n_kv_heads=get("num_key_value_heads", None),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm",
            activation="gelu",
            position="rope",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_scaling=_parse_rope_scaling(get),
            norm_eps=float(get("layer_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            parallel_block=True,
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
            lm_head_bias=True,
            rope_frac=float(get("partial_rotary_factor", 0.5)),
            # phi-1/2 qk_layernorm: one affine LayerNorm(head_dim) shared
            # across heads
            qk_norm=bool(get("qk_layernorm", False)),
            qk_norm_kind="layernorm",
        )
    if mt == "phi3":
        return _llama_like_config(get)
    if mt == "gpt2":
        h = get("n_embd")
        act = get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            raise ValueError(f"gpt2: activation_function={act!r} is not supported (gelu_new only)")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=get("n_layer"),
            n_heads=get("n_head"),
            ffn_hidden_size=get("n_inner", None) or 4 * h,
            max_seq_len=get("n_positions", 1024),
            norm="layernorm",
            activation="gelu",  # gpt2 gelu_new = tanh approx
            position="learned",
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=True,
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
        )
    if mt == "opt":
        h = get("hidden_size")
        if get("word_embed_proj_dim", h) != h:
            raise ValueError(
                "opt: word_embed_proj_dim != hidden_size (opt-350m-style "
                "embedding projection) is not supported"
            )
        if not get("do_layer_norm_before", True):
            raise ValueError("opt: post-layernorm (do_layer_norm_before=False) is not supported")
        # model_type "opt" covers relu (OPT) and gelu (Galactica) variants —
        # read the config instead of assuming, or gelu checkpoints would
        # silently run through relu
        act_map = {"relu": "relu", "gelu": "gelu_exact", "gelu_new": "gelu"}
        act = get("activation_function", "relu")
        if act not in act_map:
            raise ValueError(f"opt: activation_function={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            ffn_hidden_size=get("ffn_dim"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=act_map[act],
            position="learned",
            norm_eps=1e-5,
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
        )
    if mt == "gemma":
        act = get("hidden_activation", None) or get("hidden_act", "gelu_pytorch_tanh")
        if act != "gelu_pytorch_tanh":
            # "gelu" would mean HF's EXACT erf GELU; geglu here is tanh —
            # reject rather than silently diverge (gpt2 loader does the same)
            raise ValueError(f"gemma: hidden_activation={act!r} is not supported (gelu_pytorch_tanh only)")
        head_dim = get("head_dim", 256)
        derived = get("hidden_size") // get("num_attention_heads")
        return _llama_like_config(
            get,
            norm="rmsnorm_1p",  # zero-centered (1 + w) weights
            activation="geglu",  # gelu-gated MLP
            embed_scale=True,  # sqrt(h) embedding normalizer
            tie_embeddings=True,  # gemma always ties
            head_dim_override=int(head_dim) if int(head_dim) != derived else None,
        )
    if mt == "bloom":
        h = get("hidden_size") or get("n_embed")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=get("n_layer") or get("num_hidden_layers"),
            n_heads=get("n_head") or get("num_attention_heads"),
            ffn_hidden_size=4 * h,
            max_seq_len=get("seq_length", 2048) or 2048,
            norm="layernorm",
            activation="gelu",  # BloomGelu is the tanh approximation
            position="alibi",
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=True,  # bloom always ties lm_head to embeddings
            embed_norm=True,  # word_embeddings_layernorm
            attn_qkv_bias=True,
            attn_out_bias=True,
            mlp_bias=True,
        )
    if mt == "gptj":
        h = get("n_embd")
        act = get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            raise ValueError(f"gptj: activation_function={act!r} is not supported (gelu_new only)")
        d = h // get("n_head")
        rotary_dim = get("rotary_dim", None) or d
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=h,
            n_layers=get("n_layer"),
            n_heads=get("n_head"),
            ffn_hidden_size=get("n_inner", None) or 4 * h,
            max_seq_len=get("n_positions", 2048),
            norm="layernorm",
            activation="gelu",
            position="rope",
            # gptj's interleaved (rotate_every_two) rotary becomes the native
            # half-split convention via a load-time column permutation of
            # wq/wk (_gptj_layer) — the score q·k is permutation-invariant
            rope_frac=rotary_dim / d,
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=False,
            parallel_block=True,  # shared ln_1 feeds both branches
            mlp_bias=True,
            lm_head_bias=True,  # GPTJForCausalLM's lm_head carries a bias
        )
    if mt == "gpt_neox":
        act = get("hidden_act", "gelu")
        act_map = {
            # HF ACT2FN: "gelu" is the ERF form; the others are tanh approx
            "gelu": "gelu_exact",
            "gelu_new": "gelu",
            "gelu_fast": "gelu",
            "gelu_pytorch_tanh": "gelu",
        }
        if act not in act_map:
            raise ValueError(f"gpt_neox: hidden_act={act!r} is not supported")
        return TransformerConfig(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            n_layers=get("num_hidden_layers"),
            n_heads=get("num_attention_heads"),
            ffn_hidden_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm",
            activation=act_map[act],
            position="rope",
            rope_theta=float(get("rope_theta", None) or get("rotary_emb_base", 10000.0)),
            rope_scaling=_parse_rope_scaling(get),
            rope_frac=float(get("rotary_pct", 1.0)),
            norm_eps=float(get("layer_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            parallel_block=bool(get("use_parallel_residual", True)),
            attn_qkv_bias=bool(get("attention_bias", True)),
            attn_out_bias=bool(get("attention_bias", True)),
            mlp_bias=True,
        )
    raise ValueError(
        f"unsupported model_type {mt!r}; supported: llama, mistral, qwen2, "
        "qwen2_moe, mixtral, olmoe, falcon, phi, phi3, gpt2, gpt_neo, opt, gemma, "
        "bloom, gptj, gpt_neox, internlm, stablelm, starcoder2, "
        "qwen3, qwen3_moe, qwen3_next, jamba, exaone_moe, axk1, kimi_linear, mimo_v2_flash, "
        "longcat_flash, "
        "megatron_gpt, bert, "
        "distilbert, "
        "clip_text_model"
    )


# ---------------------------------------------------------------------------
# per-arch weight extraction
# ---------------------------------------------------------------------------
class _Taker:
    """state-dict accessor with dtype cast + [out,in]→[in,out] transpose."""

    def __init__(self, state: Dict[str, Any], dtype: str):
        self.state = state
        self.dtype = dtype

    def __call__(self, name) -> np.ndarray:
        return _np_cast(self.state.pop(name), self.dtype)

    def linear(self, name) -> np.ndarray:
        return self(name).T


def _llama_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["wq"].append(take.linear(f"{p}.self_attn.q_proj.weight"))
    layers["wk"].append(take.linear(f"{p}.self_attn.k_proj.weight"))
    layers["wv"].append(take.linear(f"{p}.self_attn.v_proj.weight"))
    layers["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    if cfg.attn_qkv_bias:
        layers["wq_b"].append(take(f"{p}.self_attn.q_proj.bias"))
        layers["wk_b"].append(take(f"{p}.self_attn.k_proj.bias"))
        layers["wv_b"].append(take(f"{p}.self_attn.v_proj.bias"))
    if cfg.attn_out_bias:
        layers["wo_b"].append(take(f"{p}.self_attn.o_proj.bias"))
    if cfg.qk_norm:
        layers["q_norm"].append(take(f"{p}.self_attn.q_norm.weight"))
        layers["k_norm"].append(take(f"{p}.self_attn.k_norm.weight"))
    if cfg.n_experts > 0:
        # qwen2-moe: router gate [E, h] + per-expert FFNs + shared expert
        layers["router"].append(take.linear(f"{p}.mlp.gate.weight"))
        for name, hf in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
            layers[name].append(
                np.stack([take.linear(f"{p}.mlp.experts.{e}.{hf}.weight") for e in range(cfg.n_experts)])
            )
        if cfg.moe_shared_expert_dim > 0:
            layers["shared_gate"].append(take.linear(f"{p}.mlp.shared_expert.gate_proj.weight"))
            layers["shared_up"].append(take.linear(f"{p}.mlp.shared_expert.up_proj.weight"))
            layers["shared_down"].append(take.linear(f"{p}.mlp.shared_expert.down_proj.weight"))
            layers["shared_gate_proj"].append(take.linear(f"{p}.mlp.shared_expert_gate.weight"))
    else:
        layers["w_gate"].append(take.linear(f"{p}.mlp.gate_proj.weight"))
        layers["w_up"].append(take.linear(f"{p}.mlp.up_proj.weight"))
        layers["w_down"].append(take.linear(f"{p}.mlp.down_proj.weight"))


def _qwen3_next_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One Qwen3-Next layer, by its kind. The checkpoint fuses what the system
    stores by use: ``q_proj`` is [nh, (d query | d gate)] and splits into wq /
    wq_gate; ``in_proj_qkvz`` is laid out by KEY head, [nk, (dk q | dk k |
    rep*dv v | rep*dv z)], and ``in_proj_ba`` [nk, (rep b | rep a)] with
    rep = value heads a key head: they regroup into gdn_qkv (q | k | v, the
    conv's channel order), gdn_z and gdn_ba (b | a). Under an expert share the
    chip's own experts are read, the router whole."""
    h = cfg.hidden_size
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    if cfg.layer_kinds[i] == "full":
        nh, d = cfg.n_heads, cfg.head_dim
        full = layers["full"]
        qg = take.linear(f"{p}.self_attn.q_proj.weight").reshape(h, nh, 2, d)
        full["wq"].append(qg[:, :, 0].reshape(h, nh * d))
        full["wq_gate"].append(qg[:, :, 1].reshape(h, nh * d))
        full["wk"].append(take.linear(f"{p}.self_attn.k_proj.weight"))
        full["wv"].append(take.linear(f"{p}.self_attn.v_proj.weight"))
        full["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
        full["q_norm"].append(take(f"{p}.self_attn.q_norm.weight"))
        full["k_norm"].append(take(f"{p}.self_attn.k_norm.weight"))
    else:
        nk, nv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        rep = nv // nk
        gdn = layers["gdn"]
        qkvz = take.linear(f"{p}.linear_attn.in_proj_qkvz.weight").reshape(h, nk, 2 * dk + 2 * rep * dv)
        q, k, v, z = np.split(qkvz, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
        gdn["gdn_qkv"].append(np.concatenate(
            [a.reshape(h, -1) for a in (q, k, v)], axis=-1))
        gdn["gdn_z"].append(z.reshape(h, nv * dv))
        ba = take.linear(f"{p}.linear_attn.in_proj_ba.weight").reshape(h, nk, 2 * rep)
        gdn["gdn_ba"].append(np.concatenate(
            [ba[:, :, :rep].reshape(h, nv), ba[:, :, rep:].reshape(h, nv)], axis=-1))
        # Conv1d weight [C, 1, K] -> [K, C]
        gdn["gdn_conv"].append(take(f"{p}.linear_attn.conv1d.weight")[:, 0, :].T)
        gdn["gdn_dt_bias"].append(take(f"{p}.linear_attn.dt_bias"))
        gdn["gdn_a_log"].append(take(f"{p}.linear_attn.A_log"))
        gdn["gdn_norm"].append(take(f"{p}.linear_attn.norm.weight"))
        gdn["gdn_out"].append(take.linear(f"{p}.linear_attn.out_proj.weight"))
    layers["router"].append(take.linear(f"{p}.mlp.gate.weight"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
        layers[name].append(np.stack([
            take.linear(f"{p}.mlp.experts.{first + e}.{hf}.weight") for e in range(cfg.n_experts)]))
    layers["shared_gate"].append(take.linear(f"{p}.mlp.shared_expert.gate_proj.weight"))
    layers["shared_up"].append(take.linear(f"{p}.mlp.shared_expert.up_proj.weight"))
    layers["shared_down"].append(take.linear(f"{p}.mlp.shared_expert.down_proj.weight"))
    layers["shared_gate_proj"].append(take.linear(f"{p}.mlp.shared_expert_gate.weight"))


def _jamba_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One Jamba layer, by its kind: ``mamba.*`` (the conv's weight [d, 1, K]
    -> [K, d], ``A_log`` [d, N] -> [N, d]: ops/state_space keeps the channels
    on the lanes) or ``self_attn.*``; then the gated MLP every layer has."""
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.pre_ff_layernorm.weight"))
    if cfg.layer_kinds[i] == "full":
        for name in ("q", "k", "v", "o"):
            layers["full"][f"w{name}"].append(take.linear(f"{p}.self_attn.{name}_proj.weight"))
    else:
        m = layers["mamba"]
        m["mamba_in"].append(take.linear(f"{p}.mamba.in_proj.weight"))
        m["mamba_conv"].append(take(f"{p}.mamba.conv1d.weight")[:, 0, :].T)
        m["mamba_conv_b"].append(take(f"{p}.mamba.conv1d.bias"))
        m["mamba_x"].append(take.linear(f"{p}.mamba.x_proj.weight"))
        m["mamba_dt"].append(take.linear(f"{p}.mamba.dt_proj.weight"))
        m["mamba_dt_b"].append(take(f"{p}.mamba.dt_proj.bias"))
        m["mamba_a_log"].append(take(f"{p}.mamba.A_log").T)
        m["mamba_d"].append(take(f"{p}.mamba.D"))
        m["mamba_out"].append(take.linear(f"{p}.mamba.out_proj.weight"))
        for name in ("dt", "b", "c"):
            m[f"mamba_{name}_norm"].append(take(f"{p}.mamba.{name}_layernorm.weight"))
    for name, hf in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
        layers[name].append(take.linear(f"{p}.feed_forward.{hf}.weight"))


def _exaone_moe_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One K-EXAONE layer: Exaone4's attention and output norms, then a dense
    MLP (the lead layers, under ``lead``) or DeepseekV3MoE's block (under
    ``sparse``: the router whole, the chip's own experts, the shared experts
    as one MLP of their summed width)."""
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_feedforward_layernorm.weight"))
    for name in ("q", "k", "v", "o"):
        layers[f"w{name}"].append(take.linear(f"{p}.self_attn.{name}_proj.weight"))
    layers["q_norm"].append(take(f"{p}.self_attn.q_norm.weight"))
    layers["k_norm"].append(take(f"{p}.self_attn.k_norm.weight"))
    names = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    if i < cfg.moe_dense_lead:
        for name, hf in names:
            layers["lead"][name].append(take.linear(f"{p}.mlp.{hf}.weight"))
        return
    moe = layers["sparse"]
    moe["router"].append(take.linear(f"{p}.mlp.gate.weight"))
    moe["router_bias"].append(take(f"{p}.mlp.gate.e_score_correction_bias"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf in names:
        moe[name].append(np.stack([
            take.linear(f"{p}.mlp.experts.{first + e}.{hf}.weight") for e in range(cfg.n_experts)]))
        moe[f"shared_{name[2:]}"].append(take.linear(f"{p}.mlp.shared_experts.{hf}.weight"))


def _axk1_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One A.X-K1 layer under DeepseekV3's checkpoint names: the latent
    attention's projections and norms, then a dense MLP (the lead layers, under
    ``lead``) or DeepseekV3MoE's block (under ``sparse``: the router whole, its
    selection bias where the router has one, the chip's own experts, the shared
    experts as one MLP of their summed width)."""
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    for name, hf in (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
                     ("wkv_b", "kv_b_proj"), ("wo", "o_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
    layers["q_a_norm"].append(take(f"{p}.self_attn.q_a_layernorm.weight"))
    layers["kv_a_norm"].append(take(f"{p}.self_attn.kv_a_layernorm.weight"))
    names = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    if i < cfg.moe_dense_lead:
        for name, hf in names:
            layers["lead"][name].append(take.linear(f"{p}.mlp.{hf}.weight"))
        return
    moe = layers["sparse"]
    moe["router"].append(take.linear(f"{p}.mlp.gate.weight"))
    if cfg.moe_router_bias:
        moe["router_bias"].append(take(f"{p}.mlp.gate.e_score_correction_bias"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf in names:
        moe[name].append(np.stack([
            take.linear(f"{p}.mlp.experts.{first + e}.{hf}.weight") for e in range(cfg.n_experts)]))
        moe[f"shared_{name[2:]}"].append(take.linear(f"{p}.mlp.shared_experts.{hf}.weight"))


def _kimi_linear_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One Kimi Linear layer, by its kind: a KDA layer's ``self_attn.*`` (q, k
    and v projections side by side as the conv's channels, the three convs'
    weights [C, 1, K] -> one [K, 3 C], the decay's and the gate's low-rank pairs)
    or a latent layer's (DeepseekV3's names, one ``q_proj``); then a dense MLP
    (the lead layers, under ``lead``) or the expert block (under ``sparse``: the
    router whole, its selection bias, the chip's own experts ``w1`` / ``w3`` /
    ``w2`` = gate / up / down, the shared experts as one MLP). The names are the
    published modeling code's as remembered: no network here to read the
    checkpoint's index."""
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    a = f"{p}.self_attn"
    if cfg.layer_kinds[i] == "full":
        for name, hf in (("wq", "q_proj"), ("wkv_a", "kv_a_proj_with_mqa"), ("wkv_b", "kv_b_proj"),
                         ("wo", "o_proj")):
            layers["full"][name].append(take.linear(f"{a}.{hf}.weight"))
        layers["full"]["kv_a_norm"].append(take(f"{a}.kv_a_layernorm.weight"))
    else:
        kda = layers["kda"]
        kda["kda_qkv"].append(np.concatenate(
            [take.linear(f"{a}.{n}_proj.weight") for n in "qkv"], axis=-1))
        kda["kda_conv"].append(np.concatenate(
            [take(f"{a}.{n}_conv1d.weight")[:, 0, :].T for n in "qkv"], axis=-1))
        kda["kda_a_log"].append(take(f"{a}.A_log").reshape(-1))
        kda["kda_dt_bias"].append(take(f"{a}.dt_bias").reshape(-1))
        for name, hf in (("kda_f_a", "f_a_proj"), ("kda_f_b", "f_b_proj"), ("kda_b", "b_proj"),
                         ("kda_g_a", "g_a_proj"), ("kda_g_b", "g_b_proj"), ("kda_out", "o_proj")):
            kda[name].append(take.linear(f"{a}.{hf}.weight"))
        kda["kda_norm"].append(take(f"{a}.o_norm.weight"))
    names = (("w_gate", "gate_proj", "w1"), ("w_up", "up_proj", "w3"), ("w_down", "down_proj", "w2"))
    if i < cfg.moe_dense_lead:
        for name, hf, _ in names:
            layers["lead"][name].append(take.linear(f"{p}.mlp.{hf}.weight"))
        return
    moe, m = layers["sparse"], f"{p}.block_sparse_moe"
    moe["router"].append(take.linear(f"{m}.gate.weight"))
    moe["router_bias"].append(take(f"{m}.gate.e_score_correction_bias"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf, w in names:
        moe[name].append(np.stack([
            take.linear(f"{m}.experts.{first + e}.{w}.weight") for e in range(cfg.n_experts)]))
        moe[f"shared_{name[2:]}"].append(take.linear(f"{m}.shared_experts.{hf}.weight"))


def _longcat_flash_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One LongCat-Flash layer: its two sub-blocks (``input_layernorm.i``,
    ``self_attn.i.*`` under DeepseekV3's projection names,
    ``post_attention_layernorm.i``, the dense ``mlps.i.*``) appended in order to
    the ``sub`` stacks, and the expert block ``mlp``: the router's classifier
    whole (identity ids too), its selection bias, the chip's own experts."""
    sub = layers["sub"]
    names = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    for i in range(2):
        sub["attn_norm"].append(take(f"{p}.input_layernorm.{i}.weight"))
        sub["mlp_norm"].append(take(f"{p}.post_attention_layernorm.{i}.weight"))
        for name, hf in (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
                         ("wkv_b", "kv_b_proj"), ("wo", "o_proj")):
            sub[name].append(take.linear(f"{p}.self_attn.{i}.{hf}.weight"))
        sub["q_a_norm"].append(take(f"{p}.self_attn.{i}.q_a_layernorm.weight"))
        sub["kv_a_norm"].append(take(f"{p}.self_attn.{i}.kv_a_layernorm.weight"))
        for name, hf in names:
            sub[name].append(take.linear(f"{p}.mlps.{i}.{hf}.weight"))
    layers["router"].append(take.linear(f"{p}.mlp.router.classifier.weight"))
    layers["router_bias"].append(take(f"{p}.mlp.router.e_score_correction_bias"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf in names:
        layers[name].append(np.stack([
            take.linear(f"{p}.mlp.experts.{first + e}.{hf}.weight") for e in range(cfg.n_experts)]))


def _mimo_v2_flash_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    """One MiMo-V2-Flash layer: the llama layer's norms and projections, its
    attention under its KIND's stack (``full`` / ``window``, the window layers
    with their ``attention_sink_bias`` [heads]), then a dense MLP (the lead
    layers, under ``lead``) or DeepseekV3MoE's block with no shared expert
    (under ``sparse``: the router whole, its selection bias, the chip's own
    experts). The names are the published modeling code's as remembered: no
    network here to read the checkpoint's index."""
    i = int(p.rsplit(".", 1)[1])
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    attn = layers["window" if cfg.attn_layer_pattern[i] else "full"]
    for name in ("q", "k", "v", "o"):
        attn[f"w{name}"].append(take.linear(f"{p}.self_attn.{name}_proj.weight"))
    if "sink" in attn:
        attn["sink"].append(take(f"{p}.self_attn.attention_sink_bias"))
    names = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    if i < cfg.moe_dense_lead:
        for name, hf in names:
            layers["lead"][name].append(take.linear(f"{p}.mlp.{hf}.weight"))
        return
    moe = layers["sparse"]
    moe["router"].append(take.linear(f"{p}.mlp.gate.weight"))
    moe["router_bias"].append(take(f"{p}.mlp.gate.e_score_correction_bias"))
    first = cfg.moe_expert_shard * cfg.n_experts
    for name, hf in names:
        moe[name].append(np.stack([
            take.linear(f"{p}.mlp.experts.{first + e}.{hf}.weight") for e in range(cfg.n_experts)]))


def _phi3_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # phi-3 fuses qkv_proj [q;k;v] and gate_up_proj [gate;up] — split rows
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    qkv = take(f"{p}.self_attn.qkv_proj.weight")  # [(nh+2*nkv)*d, h]
    q_rows = cfg.n_heads * cfg.head_dim
    kv_rows = cfg.kv_heads * cfg.head_dim
    layers["wq"].append(qkv[:q_rows].T)
    layers["wk"].append(qkv[q_rows : q_rows + kv_rows].T)
    layers["wv"].append(qkv[q_rows + kv_rows :].T)
    layers["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    gate_up = take(f"{p}.mlp.gate_up_proj.weight")  # [2*ffn, h]
    ffn = gate_up.shape[0] // 2
    layers["w_gate"].append(gate_up[:ffn].T)
    layers["w_up"].append(gate_up[ffn:].T)
    layers["w_down"].append(take.linear(f"{p}.mlp.down_proj.weight"))


def _split_falcon_qkv(fused: np.ndarray, cfg: TransformerConfig) -> Tuple[np.ndarray, ...]:
    """De-interleave falcon's fused query_key_value rows.

    Every falcon layout is the per-kv-group interleave
    [q·(nh/nkv), k, v] — HF's legacy ``_split_heads`` views are its
    degenerate cases: MHA is group-of-3 per head (view ``(nh, 3, d)``) and
    multi_query is nkv=1 (all q rows, then k, then v). fused: [rows, h]
    (or [rows] for the bias). Returns (q, k, v) row-major.
    """
    d, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.kv_heads
    group = nh // nkv + 2
    blocks = fused.reshape(nkv, group, d, *fused.shape[1:])
    q = blocks[:, :-2].reshape(nh * d, *fused.shape[1:])
    k = blocks[:, -2].reshape(nkv * d, *fused.shape[1:])
    v = blocks[:, -1].reshape(nkv * d, *fused.shape[1:])
    return q, k, v


def _falcon_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    if f"{p}.ln_attn.weight" in take.state:  # new_decoder_architecture
        layers["attn_norm"].append(take(f"{p}.ln_attn.weight"))
        layers["attn_norm_b"].append(take(f"{p}.ln_attn.bias"))
        layers["mlp_norm"].append(take(f"{p}.ln_mlp.weight"))
        layers["mlp_norm_b"].append(take(f"{p}.ln_mlp.bias"))
    else:
        ln_w = take(f"{p}.input_layernorm.weight")
        ln_b = take(f"{p}.input_layernorm.bias")
        layers["attn_norm"].append(ln_w)
        layers["attn_norm_b"].append(ln_b)
        if cfg.parallel_block:
            # falcon-7b shares one norm across both branches
            layers["mlp_norm"].append(ln_w)
            layers["mlp_norm_b"].append(ln_b)
        else:
            layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
            layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    q, k, v = _split_falcon_qkv(take(f"{p}.self_attention.query_key_value.weight"), cfg)
    layers["wq"].append(q.T)
    layers["wk"].append(k.T)
    layers["wv"].append(v.T)
    layers["wo"].append(take.linear(f"{p}.self_attention.dense.weight"))
    layers["w_up"].append(take.linear(f"{p}.mlp.dense_h_to_4h.weight"))
    layers["w_down"].append(take.linear(f"{p}.mlp.dense_4h_to_h.weight"))
    if cfg.attn_qkv_bias:
        qb, kb, vb = _split_falcon_qkv(take(f"{p}.self_attention.query_key_value.bias"), cfg)
        layers["wq_b"].append(qb)
        layers["wk_b"].append(kb)
        layers["wv_b"].append(vb)
        layers["wo_b"].append(take(f"{p}.self_attention.dense.bias"))
        layers["w_up_b"].append(take(f"{p}.mlp.dense_h_to_4h.bias"))
        layers["w_down_b"].append(take(f"{p}.mlp.dense_4h_to_h.bias"))


def _phi_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # phi: one shared input_layernorm feeds both parallel branches
    ln_w = take(f"{p}.input_layernorm.weight")
    ln_b = take(f"{p}.input_layernorm.bias")
    layers["attn_norm"].append(ln_w)
    layers["attn_norm_b"].append(ln_b)
    layers["mlp_norm"].append(ln_w)
    layers["mlp_norm_b"].append(ln_b)
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
        layers[f"{name}_b"].append(take(f"{p}.self_attn.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.self_attn.dense.weight"))
    layers["wo_b"].append(take(f"{p}.self_attn.dense.bias"))
    if cfg.qk_norm:
        layers["q_norm"].append(take(f"{p}.self_attn.q_layernorm.weight"))
        layers["q_norm_b"].append(take(f"{p}.self_attn.q_layernorm.bias"))
        layers["k_norm"].append(take(f"{p}.self_attn.k_layernorm.weight"))
        layers["k_norm_b"].append(take(f"{p}.self_attn.k_layernorm.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.fc1.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.fc1.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.fc2.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.fc2.bias"))


def _gpt2_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # GPT-2 Conv1D stores [in, out] — NO transpose; c_attn fuses qkv columns
    layers["attn_norm"].append(take(f"{p}.ln_1.weight"))
    layers["attn_norm_b"].append(take(f"{p}.ln_1.bias"))
    h = cfg.hidden_size
    w = take(f"{p}.attn.c_attn.weight")  # [h, 3h]
    b = take(f"{p}.attn.c_attn.bias")  # [3h]
    layers["wq"].append(w[:, :h])
    layers["wk"].append(w[:, h : 2 * h])
    layers["wv"].append(w[:, 2 * h :])
    layers["wq_b"].append(b[:h])
    layers["wk_b"].append(b[h : 2 * h])
    layers["wv_b"].append(b[2 * h :])
    layers["wo"].append(take(f"{p}.attn.c_proj.weight"))
    layers["wo_b"].append(take(f"{p}.attn.c_proj.bias"))
    layers["mlp_norm"].append(take(f"{p}.ln_2.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.ln_2.bias"))
    layers["w_up"].append(take(f"{p}.mlp.c_fc.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.c_fc.bias"))
    layers["w_down"].append(take(f"{p}.mlp.c_proj.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.c_proj.bias"))


def _opt_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    layers["attn_norm"].append(take(f"{p}.self_attn_layer_norm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.self_attn_layer_norm.bias"))
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
        layers[f"{name}_b"].append(take(f"{p}.self_attn.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.self_attn.out_proj.weight"))
    layers["wo_b"].append(take(f"{p}.self_attn.out_proj.bias"))
    layers["mlp_norm"].append(take(f"{p}.final_layer_norm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.final_layer_norm.bias"))
    layers["w_up"].append(take.linear(f"{p}.fc1.weight"))
    layers["w_up_b"].append(take(f"{p}.fc1.bias"))
    layers["w_down"].append(take.linear(f"{p}.fc2.weight"))
    layers["w_down_b"].append(take(f"{p}.fc2.bias"))


def _mixtral_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # llama attention + block-sparse MoE: w1=gate, w3=up, w2=down
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["wq"].append(take.linear(f"{p}.self_attn.q_proj.weight"))
    layers["wk"].append(take.linear(f"{p}.self_attn.k_proj.weight"))
    layers["wv"].append(take.linear(f"{p}.self_attn.v_proj.weight"))
    layers["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["router"].append(take.linear(f"{p}.block_sparse_moe.gate.weight"))
    for name, hf in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
        layers[name].append(
            np.stack([
                take.linear(f"{p}.block_sparse_moe.experts.{e}.{hf}.weight")
                for e in range(cfg.n_experts)
            ])
        )


def _stablelm_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    ln_w = take(f"{p}.input_layernorm.weight")
    ln_b = take(f"{p}.input_layernorm.bias")
    layers["attn_norm"].append(ln_w)
    layers["attn_norm_b"].append(ln_b)
    if cfg.parallel_block:
        # parallel residual shares input_layernorm (gpt-j-style)
        layers["mlp_norm"].append(ln_w)
        layers["mlp_norm_b"].append(ln_b)
    else:
        layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
        layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
        if cfg.attn_qkv_bias:
            layers[f"{name}_b"].append(take(f"{p}.self_attn.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
    if cfg.qk_norm:
        # stablelm-2 qk_layernorm: a ModuleList of biasless per-head
        # LayerNorms — stack the [d] weights into [n_heads, d]
        layers["q_norm"].append(
            np.stack([take(f"{p}.self_attn.q_layernorm.norms.{h}.weight") for h in range(cfg.n_heads)])
        )
        layers["k_norm"].append(
            np.stack([take(f"{p}.self_attn.k_layernorm.norms.{h}.weight") for h in range(cfg.kv_heads)])
        )
    layers["w_gate"].append(take.linear(f"{p}.mlp.gate_proj.weight"))
    layers["w_up"].append(take.linear(f"{p}.mlp.up_proj.weight"))
    layers["w_down"].append(take.linear(f"{p}.mlp.down_proj.weight"))


def _starcoder2_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.input_layernorm.bias"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
        if cfg.attn_qkv_bias:  # use_bias=False checkpoints ship no biases
            layers[f"{name}_b"].append(take(f"{p}.self_attn.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.self_attn.o_proj.weight"))
    if cfg.attn_out_bias:
        layers["wo_b"].append(take(f"{p}.self_attn.o_proj.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.c_fc.weight"))
    layers["w_down"].append(take.linear(f"{p}.mlp.c_proj.weight"))
    if cfg.mlp_bias:
        layers["w_up_b"].append(take(f"{p}.mlp.c_fc.bias"))
        layers["w_down_b"].append(take(f"{p}.mlp.c_proj.bias"))


def _bloom_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # bloom: MHA with per-head [q,k,v] interleaved fused qkv — the falcon
    # MHA degenerate case (group-of-3 per head) splits it
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.input_layernorm.bias"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    q, k, v = _split_falcon_qkv(take(f"{p}.self_attention.query_key_value.weight"), cfg)
    layers["wq"].append(q.T)
    layers["wk"].append(k.T)
    layers["wv"].append(v.T)
    qb, kb, vb = _split_falcon_qkv(take(f"{p}.self_attention.query_key_value.bias"), cfg)
    layers["wq_b"].append(qb)
    layers["wk_b"].append(kb)
    layers["wv_b"].append(vb)
    layers["wo"].append(take.linear(f"{p}.self_attention.dense.weight"))
    layers["wo_b"].append(take(f"{p}.self_attention.dense.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.dense_h_to_4h.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.dense_h_to_4h.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.dense_4h_to_h.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.dense_4h_to_h.bias"))


def _gptj_rope_perm(w: np.ndarray, cfg: TransformerConfig) -> np.ndarray:
    """Permute a [h, nh*d] projection's per-head rotary columns from gptj's
    interleaved (rotate_every_two) layout to the half-split layout: new
    column i ← old 2i, new rot/2+i ← old 2i+1. Scores are invariant because
    q and k get the SAME permutation."""
    d = cfg.head_dim
    rot = (int(d * cfg.rope_frac) // 2) * 2
    perm = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2), np.arange(rot, d)])
    cols = w.reshape(w.shape[0], cfg.n_heads, d)
    return cols[:, :, perm].reshape(w.shape)


def _gptj_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    ln_w = take(f"{p}.ln_1.weight")
    ln_b = take(f"{p}.ln_1.bias")
    layers["attn_norm"].append(ln_w)
    layers["attn_norm_b"].append(ln_b)
    layers["mlp_norm"].append(ln_w)  # shared norm feeds both parallel branches
    layers["mlp_norm_b"].append(ln_b)
    layers["wq"].append(_gptj_rope_perm(take.linear(f"{p}.attn.q_proj.weight"), cfg))
    layers["wk"].append(_gptj_rope_perm(take.linear(f"{p}.attn.k_proj.weight"), cfg))
    layers["wv"].append(take.linear(f"{p}.attn.v_proj.weight"))
    layers["wo"].append(take.linear(f"{p}.attn.out_proj.weight"))
    layers["w_up"].append(take.linear(f"{p}.mlp.fc_in.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.fc_in.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.fc_out.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.fc_out.bias"))


def _clip_text_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    layers["attn_norm"].append(take(f"{p}.layer_norm1.weight"))
    layers["attn_norm_b"].append(take(f"{p}.layer_norm1.bias"))
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.self_attn.{hf}.weight"))
        layers[f"{name}_b"].append(take(f"{p}.self_attn.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.self_attn.out_proj.weight"))
    layers["wo_b"].append(take(f"{p}.self_attn.out_proj.bias"))
    layers["mlp_norm"].append(take(f"{p}.layer_norm2.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.layer_norm2.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.fc1.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.fc1.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.fc2.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.fc2.bias"))


def _bert_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # post-LN encoder: attention.output.LayerNorm normalizes x + attn(x)
    # (→ attn_norm), output.LayerNorm normalizes + mlp (→ mlp_norm)
    for name, hf in (("wq", "query"), ("wk", "key"), ("wv", "value")):
        layers[name].append(take.linear(f"{p}.attention.self.{hf}.weight"))
        layers[f"{name}_b"].append(take(f"{p}.attention.self.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.attention.output.dense.weight"))
    layers["wo_b"].append(take(f"{p}.attention.output.dense.bias"))
    layers["attn_norm"].append(take(f"{p}.attention.output.LayerNorm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.attention.output.LayerNorm.bias"))
    layers["w_up"].append(take.linear(f"{p}.intermediate.dense.weight"))
    layers["w_up_b"].append(take(f"{p}.intermediate.dense.bias"))
    layers["w_down"].append(take.linear(f"{p}.output.dense.weight"))
    layers["w_down_b"].append(take(f"{p}.output.dense.bias"))
    layers["mlp_norm"].append(take(f"{p}.output.LayerNorm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.output.LayerNorm.bias"))


def _distilbert_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    for name, hf in (("wq", "q_lin"), ("wk", "k_lin"), ("wv", "v_lin")):
        layers[name].append(take.linear(f"{p}.attention.{hf}.weight"))
        layers[f"{name}_b"].append(take(f"{p}.attention.{hf}.bias"))
    layers["wo"].append(take.linear(f"{p}.attention.out_lin.weight"))
    layers["wo_b"].append(take(f"{p}.attention.out_lin.bias"))
    layers["attn_norm"].append(take(f"{p}.sa_layer_norm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.sa_layer_norm.bias"))
    layers["w_up"].append(take.linear(f"{p}.ffn.lin1.weight"))
    layers["w_up_b"].append(take(f"{p}.ffn.lin1.bias"))
    layers["w_down"].append(take.linear(f"{p}.ffn.lin2.weight"))
    layers["w_down_b"].append(take(f"{p}.ffn.lin2.bias"))
    layers["mlp_norm"].append(take(f"{p}.output_layer_norm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.output_layer_norm.bias"))


def _gptneo_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # GPT-Neo uses plain Linears ([out, in] — transpose), unlike gpt2's
    # Conv1D; q/k/v carry NO bias, out_proj and the MLP do
    layers["attn_norm"].append(take(f"{p}.ln_1.weight"))
    layers["attn_norm_b"].append(take(f"{p}.ln_1.bias"))
    for name, hf in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
        layers[name].append(take.linear(f"{p}.attn.attention.{hf}.weight"))
    layers["wo"].append(take.linear(f"{p}.attn.attention.out_proj.weight"))
    layers["wo_b"].append(take(f"{p}.attn.attention.out_proj.bias"))
    layers["mlp_norm"].append(take(f"{p}.ln_2.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.ln_2.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.c_fc.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.c_fc.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.c_proj.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.c_proj.bias"))


def _megatron_gpt_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    # megatron fuses qkv per head ([q_h, k_h, v_h] blocks) — the falcon MHA
    # de-interleave (group-of-3 per head) recovers row-major q/k/v
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.input_layernorm.bias"))
    q, k, v = _split_falcon_qkv(take(f"{p}.attention.query_key_value.weight"), cfg)
    layers["wq"].append(q.T)
    layers["wk"].append(k.T)
    layers["wv"].append(v.T)
    qb, kb, vb = _split_falcon_qkv(take(f"{p}.attention.query_key_value.bias"), cfg)
    layers["wq_b"].append(qb)
    layers["wk_b"].append(kb)
    layers["wv_b"].append(vb)
    layers["wo"].append(take.linear(f"{p}.attention.dense.weight"))
    layers["wo_b"].append(take(f"{p}.attention.dense.bias"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.dense_h_to_4h.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.dense_h_to_4h.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.dense_4h_to_h.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.dense_4h_to_h.bias"))


def _gptneox_layer(take: _Taker, cfg: TransformerConfig, p: str, layers: Dict[str, list]):
    layers["attn_norm"].append(take(f"{p}.input_layernorm.weight"))
    layers["attn_norm_b"].append(take(f"{p}.input_layernorm.bias"))
    layers["mlp_norm"].append(take(f"{p}.post_attention_layernorm.weight"))
    layers["mlp_norm_b"].append(take(f"{p}.post_attention_layernorm.bias"))
    q, k, v = _split_falcon_qkv(take(f"{p}.attention.query_key_value.weight"), cfg)
    layers["wq"].append(q.T)
    layers["wk"].append(k.T)
    layers["wv"].append(v.T)
    if cfg.attn_qkv_bias:
        qb, kb, vb = _split_falcon_qkv(take(f"{p}.attention.query_key_value.bias"), cfg)
        layers["wq_b"].append(qb)
        layers["wk_b"].append(kb)
        layers["wv_b"].append(vb)
    layers["wo"].append(take.linear(f"{p}.attention.dense.weight"))
    if cfg.attn_out_bias:
        layers["wo_b"].append(take(f"{p}.attention.dense.bias"))
    layers["w_up"].append(take.linear(f"{p}.mlp.dense_h_to_4h.weight"))
    layers["w_up_b"].append(take(f"{p}.mlp.dense_h_to_4h.bias"))
    layers["w_down"].append(take.linear(f"{p}.mlp.dense_4h_to_h.weight"))
    layers["w_down_b"].append(take(f"{p}.mlp.dense_4h_to_h.bias"))


_LAYER_EXTRACTORS: Dict[str, Callable] = {
    "llama": _llama_layer,
    "mistral": _llama_layer,
    "qwen2": _llama_layer,
    "qwen2_moe": _llama_layer,
    "qwen3": _llama_layer,
    "qwen3_next": _qwen3_next_layer,
    "jamba": _jamba_layer,
    "exaone_moe": _exaone_moe_layer,
    "axk1": _axk1_layer,
    "kimi_linear": _kimi_linear_layer,
    "mimo_v2_flash": _mimo_v2_flash_layer,
    "longcat_flash": _longcat_flash_layer,
    "qwen3_moe": _llama_layer,
    "falcon": _falcon_layer,
    "phi": _phi_layer,
    "phi3": _phi3_layer,
    "bert": _bert_layer,
    "clip_text_model": _clip_text_layer,
    "distilbert": _distilbert_layer,
    "gpt2": _gpt2_layer,
    "gpt_neo": _gptneo_layer,
    "internlm": _llama_layer,
    "opt": _opt_layer,
    "gemma": _llama_layer,  # same checkpoint layout as llama
    "bloom": _bloom_layer,
    "gptj": _gptj_layer,
    "gpt_neox": _gptneox_layer,
    "megatron_gpt": _megatron_gpt_layer,
    "mixtral": _mixtral_layer,
    # olmoe's checkpoint names are the llama layer's with qwen3's q_norm /
    # k_norm (here [nh*d] / [nkv*d] wide) and qwen2_moe's mlp.gate +
    # mlp.experts.{e}.{gate,up,down}_proj: the same extractor reads them
    "olmoe": _llama_layer,
    "stablelm": _stablelm_layer,
    "starcoder2": _starcoder2_layer,
}

# per-arch (embed key, final-norm key, layer prefix, pos-embed key or None)
_TOPLEVEL_KEYS: Dict[str, Tuple[str, str, str, Optional[str]]] = {
    "llama": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "mistral": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "qwen2": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "qwen2_moe": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "qwen3": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "qwen3_next": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "jamba": ("model.embed_tokens.weight", "model.final_layernorm", "model.layers", None),
    "exaone_moe": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "axk1": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "kimi_linear": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "mimo_v2_flash": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "longcat_flash": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "qwen3_moe": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "phi3": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "phi": ("model.embed_tokens.weight", "model.final_layernorm", "model.layers", None),
    "falcon": ("transformer.word_embeddings.weight", "transformer.ln_f", "transformer.h", None),
    "gpt2": ("transformer.wte.weight", "transformer.ln_f", "transformer.h", "transformer.wpe.weight"),
    "gpt_neo": ("transformer.wte.weight", "transformer.ln_f", "transformer.h", "transformer.wpe.weight"),
    "internlm": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "opt": (
        "model.decoder.embed_tokens.weight",
        "model.decoder.final_layer_norm",
        "model.decoder.layers",
        "model.decoder.embed_positions.weight",
    ),
    "gemma": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "bloom": ("transformer.word_embeddings.weight", "transformer.ln_f", "transformer.h", None),
    "gptj": ("transformer.wte.weight", "transformer.ln_f", "transformer.h", None),
    "gpt_neox": ("gpt_neox.embed_in.weight", "gpt_neox.final_layer_norm", "gpt_neox.layers", None),
    "megatron_gpt": (
        "word_embeddings.weight",
        "transformer.final_layernorm",
        "transformer.layers",
        "position_embeddings.weight",
    ),
    "clip_text_model": (
        "text_model.embeddings.token_embedding.weight",
        "text_model.final_layer_norm",
        "text_model.encoder.layers",
        "text_model.embeddings.position_embedding.weight",
    ),
    "mixtral": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "olmoe": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "stablelm": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
    "starcoder2": ("model.embed_tokens.weight", "model.norm", "model.layers", None),
}


def _expected_layer_keys(cfg: TransformerConfig) -> Dict[str, list]:
    """Empty stacking lists for exactly the keys this config's params carry."""
    keys = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_up", "w_down"]
    if cfg.latent:
        from deepspeed_tpu.models.transformer import latent_keys

        keys = [k for k in keys if k not in ("wq", "wk", "wv")] + list(latent_keys(cfg))
    if cfg.activation in ("swiglu", "geglu"):
        keys.append("w_gate")
    if cfg.norm == "layernorm":
        keys += ["attn_norm_b", "mlp_norm_b"]
    if cfg.attn_qkv_bias:
        keys += ["wq_b", "wk_b", "wv_b"]
    if cfg.attn_out_bias:
        keys.append("wo_b")
    if cfg.qk_norm:
        keys += ["q_norm", "k_norm"]
        if cfg.qk_norm_kind == "layernorm":
            keys += ["q_norm_b", "k_norm_b"]
    if cfg.mlp_bias and cfg.n_experts == 0:
        keys += ["w_up_b", "w_down_b"] + (["w_gate_b"] if cfg.activation in ("swiglu", "geglu") else [])
    if cfg.attn_out_gate:
        keys.append("wq_gate")
    if cfg.n_experts > 0:
        keys.append("router")
        if cfg.router_has_bias:
            keys.append("router_bias")
        if cfg.moe_shared_expert_dim > 0:
            keys += ["shared_gate", "shared_up", "shared_down"]
            if cfg.moe_shared_gated:
                keys.append("shared_gate_proj")
    if cfg.moe_shortcut:  # the expert block's keys, and a sub-block's (a dense MLP of its own) apart
        mlp = ("w_up", "w_down", "w_gate")
        return {**{k: [] for k in keys if k.startswith("router") or k in mlp},
                "sub": {k: [] for k in keys if not k.startswith("router")}}
    if cfg.moe_dense_lead:  # the MLPs stacked apart (transformer.init_params)
        mlp = ("w_up", "w_down", "w_gate")
        out = {k: [] for k in keys if k not in mlp and not k.startswith(("router", "shared_"))}
        out["lead"] = {k: [] for k in mlp}
        out["sparse"] = {k: [] for k in keys if k in mlp or k.startswith(("router", "shared_"))}
        if cfg.attn_by_kind:  # ... and the attention by kind, the window layers' with their sinks
            from deepspeed_tpu.models.transformer import ATTENTION_KEYS

            attn = [k for k in out if k in ATTENTION_KEYS]
            out["full"] = {k: out.pop(k) for k in attn}
            out["window"] = {k: [] for k in attn + (["sink"] if cfg.attn_sink_window else [])}
    else:
        out = {k: [] for k in keys}
    if cfg.hybrid:  # stacked by kind (transformer.init_params)
        from deepspeed_tpu.models.transformer import ATTENTION_KEYS, LATENT_KEYS

        attn = [k for k in out if k in ATTENTION_KEYS or k in LATENT_KEYS]
        out["full"] = {k: out.pop(k) for k in attn}
        own = {"gdn": ("gdn_qkv", "gdn_z", "gdn_ba", "gdn_conv", "gdn_dt_bias", "gdn_a_log",
                       "gdn_norm", "gdn_out"),
               "mamba": ("mamba_in", "mamba_conv", "mamba_conv_b", "mamba_x", "mamba_dt_norm",
                         "mamba_b_norm", "mamba_c_norm", "mamba_dt", "mamba_dt_b", "mamba_a_log",
                         "mamba_d", "mamba_out"),
               "kda": ("kda_qkv", "kda_conv", "kda_a_log", "kda_dt_bias", "kda_f_a", "kda_f_b",
                       "kda_b", "kda_g_a", "kda_g_b", "kda_norm", "kda_out")}[cfg.recurrent_kind]
        out[cfg.recurrent_kind] = {k: [] for k in own}
    return out


def _load_encoder(mt: str, cfg: TransformerConfig, take: _Taker, state: Dict[str, Any]):
    """bert / distilbert (reference module_inject/containers/{bert,
    distil_bert}.py): post-LN encoder stack + masked-LM head. A bare
    BertModel checkpoint (no cls.predictions / vocab_transform) loads with
    mlm_head=False and returns the final hidden states from forward_hidden."""
    # BertForMaskedLM prefixes the backbone with "bert." / "distilbert.";
    # a bare BertModel/DistilBertModel checkpoint saves root-level keys
    base = "" if "embeddings.word_embeddings.weight" in state else f"{mt}."
    stem = f"{base}embeddings"
    prefix = f"{base}encoder.layer" if mt == "bert" else f"{base}transformer.layer"
    head_probe = "cls.predictions.transform.dense.weight" if mt == "bert" else "vocab_transform.weight"
    if head_probe not in state:
        cfg = dataclasses.replace(cfg, mlm_head=False)
    layers = _expected_layer_keys(cfg)
    extract = _LAYER_EXTRACTORS[mt]
    for i in range(cfg.n_layers):
        extract(take, cfg, f"{prefix}.{i}", layers)
    params: Dict[str, Any] = {
        "embed": take(f"{stem}.word_embeddings.weight"),
        "pos_embed": take(f"{stem}.position_embeddings.weight"),
        "embed_norm": take(f"{stem}.LayerNorm.weight"),
        "embed_norm_b": take(f"{stem}.LayerNorm.bias"),
        "layers": {k: np.stack(v) for k, v in layers.items()},
    }
    if cfg.type_vocab_size > 0:
        params["type_embed"] = take(f"{stem}.token_type_embeddings.weight")
    if cfg.mlm_head:
        if mt == "bert":
            params["mlm_dense"] = take.linear("cls.predictions.transform.dense.weight")
            params["mlm_dense_b"] = take("cls.predictions.transform.dense.bias")
            params["mlm_norm"] = take("cls.predictions.transform.LayerNorm.weight")
            params["mlm_norm_b"] = take("cls.predictions.transform.LayerNorm.bias")
            params["mlm_bias"] = take("cls.predictions.bias")
            state.pop("cls.predictions.decoder.weight", None)  # tied alias
            state.pop("cls.predictions.decoder.bias", None)  # alias of cls.predictions.bias
        else:
            params["mlm_dense"] = take.linear("vocab_transform.weight")
            params["mlm_dense_b"] = take("vocab_transform.bias")
            params["mlm_norm"] = take("vocab_layer_norm.weight")
            params["mlm_norm_b"] = take("vocab_layer_norm.bias")
            params["mlm_bias"] = take("vocab_projector.bias")
            state.pop("vocab_projector.weight", None)  # tied alias
    leftover = [
        k for k in state
        if not k.endswith("position_ids")  # non-persistent HF buffer
    ]
    if leftover:
        logger.warning(f"unmapped HF weights ignored: {leftover[:8]}{'...' if len(leftover) > 8 else ''}")
    return cfg, params


def load_hf_model(
    model_name_or_path: str,
    dtype: str = "bfloat16",
) -> Tuple[TransformerConfig, Dict[str, Any]]:
    """Load a supported HF checkpoint directory into the native family's
    stacked layout. Returns (config, params) — feed them to
    ``make_loss_fn(config)`` + ``initialize(model_parameters=params)`` or the
    inference engines."""
    cfg_path = os.path.join(model_name_or_path, "config.json")
    if not os.path.isfile(cfg_path):
        raise FileNotFoundError(
            f"{model_name_or_path} is not a checkpoint dir (no config.json); "
            "download/snapshot the model first — there is no network access at load time"
        )
    hf_cfg = json.load(open(cfg_path))
    mt = hf_cfg.get("model_type", "llama")
    if mt not in _LAYER_EXTRACTORS:
        raise ValueError(f"unsupported model_type {mt!r}; supported: {sorted(_LAYER_EXTRACTORS)}")
    cfg = dataclass_replace(config_from_hf(hf_cfg), dtype=dtype)
    state = _load_state_dict(model_name_or_path)
    take = _Taker(state, dtype)

    if mt in ("bert", "distilbert"):
        return _load_encoder(mt, cfg, take, state)

    embed_key, norm_key, layer_prefix, pos_key = _TOPLEVEL_KEYS[mt]
    extract = _LAYER_EXTRACTORS[mt]
    layers = _expected_layer_keys(cfg)
    for i in range(cfg.n_layers):
        extract(take, cfg, f"{layer_prefix}.{i}", layers)

    params: Dict[str, Any] = {
        "embed": take(embed_key),
        "final_norm": take(f"{norm_key}.weight"),
        "layers": {k: ({n: np.stack(a) for n, a in v.items()} if isinstance(v, dict)
                       else np.stack(v)) for k, v in layers.items()},
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = take(f"{norm_key}.bias")
    if cfg.position == "learned":
        pe = take(pos_key)
        if mt == "opt":
            pe = pe[2:]  # OPT offsets learned positions by 2
        params["pos_embed"] = pe
    if cfg.embed_norm:
        params["embed_norm"] = take("transformer.word_embeddings_layernorm.weight")
        params["embed_norm_b"] = take("transformer.word_embeddings_layernorm.bias")
    if not cfg.tie_embeddings:
        if "embed_out.weight" in state:  # gpt_neox names its lm_head embed_out
            params["lm_head"] = take.linear("embed_out.weight")
        elif "lm_head.weight" in state:
            params["lm_head"] = take.linear("lm_head.weight")
            if cfg.lm_head_bias:
                params["lm_head_b"] = take("lm_head.bias")
        elif cfg.lm_head_bias:
            raise ValueError("checkpoint declares a biased lm_head but ships no lm_head.weight")
        else:
            logger.warning("no lm_head.weight in checkpoint; tying to embeddings")
            cfg = dataclass_replace(cfg, tie_embeddings=True)
    else:
        state.pop("lm_head.weight", None)
    # qwen3_next: the multi-token-prediction head is no part of the causal LM
    # (transformers ignores mtp.* on load), and an expert share leaves the
    # other chips' experts where they are
    leftover = [k for k in state if not k.endswith("rotary_emb.inv_freq")
                and not k.startswith(("mtp.", "model.mtp")) and not (cfg.moe_experts_total and ".experts." in k)]
    if leftover:
        logger.warning(f"unmapped HF weights ignored: {leftover[:8]}{'...' if len(leftover) > 8 else ''}")
    return cfg, params


# legacy name (round-1 API); the registry now handles every supported arch
load_hf_llama = load_hf_model
