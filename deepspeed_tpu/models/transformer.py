"""TPU-native decoder-only transformer family (the flagship model).

The reference framework wraps user torch models; the TPU build additionally
ships a first-class model family (the analogue of the model zoo the reference
targets through HF + module_inject containers: llama/gpt2/opt/bloom — see
deepspeed/module_inject/containers/). One config covers:

  * Llama-style: RMSNorm, RoPE, SwiGLU, grouped-query attention
  * GPT-2-style: LayerNorm, learned positions, GELU MLP, tied embeddings

TPU-first design decisions:
  * Layer parameters are STACKED along a leading [n_layers, ...] dim and the
    forward is a single ``lax.scan`` over layers — compile time is flat in
    depth and XLA pipelines the layer loop.
  * All weights live in a flat dict pytree; sharding is declared as a
    parallel pytree of ``PartitionSpec`` (``param_partition_specs``) that
    composes Megatron-style tensor parallelism (``model`` axis) with ZeRO
    (``data`` axis added by runtime/zero/partition.py) — the AutoTP analogue
    (module_inject/auto_tp.py:193) done declaratively.
  * Activations carry ``with_sharding_constraint`` on [batch, seq, hidden]:
    batch over data/expert, seq over sequence (Ulysses), hidden replicated.
  * Attention dispatches to the Pallas flash kernel (ops/attention) on TPU.
  * ``remat``: per-layer ``jax.checkpoint``; the default policy keeps the
    outputs of the layer's matrix products and the attention kernel's output
    and log-sum-exp (``remat_policy``) — the activation-checkpointing
    analogue (runtime/activation_checkpointing/checkpointing.py:488) without
    RNG state juggling (jax threads RNG keys).
  * Sequence parallelism: when the mesh's ``sequence`` axis > 1 the attention
    runs under Ulysses all-to-all (parallel/sequence/ulysses.py), scattering
    heads and gathering sequence exactly like the reference
    ``DistributedAttention`` (sequence/layer.py:331).
"""

import dataclasses
import functools
import math
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.ops.attention import attention as attention_op
from deepspeed_tpu.ops.attention import head_major, heads_flat, heads_view, token_major, tokens_view
from deepspeed_tpu.parallel.topology import (
    BATCH_AXES,
    CONTEXT_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    constrain,
    get_topology,
)

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}

# attn_sparsity spec kinds → SparsityConfig families (ops/sparse_attention)
_SPARSITY_KINDS = ("dense", "fixed", "bigbird", "bslongformer", "variable")


@functools.lru_cache(maxsize=32)
def _sparsity_schedule(spec, n_heads, seq_len, block, causal):
    """attn_sparsity spec → compacted BlockSchedule. lru-cached so the
    schedule is built once per (spec, seq_len) and reused across every
    trace — a trace-time constant, never recomputed per step. The model's
    causal flag is ANDed in: a bidirectional sparsity family under a
    causal LM must not leak future positions."""
    from deepspeed_tpu.ops.sparse_attention import config as sa_config
    from deepspeed_tpu.ops.sparse_attention import schedule_from_layout

    cls = {
        "dense": sa_config.DenseSparsityConfig,
        "fixed": sa_config.FixedSparsityConfig,
        "bigbird": sa_config.BigBirdSparsityConfig,
        "bslongformer": sa_config.BSLongformerSparsityConfig,
        "variable": sa_config.VariableSparsityConfig,
    }[spec[0]]
    kwargs = dict(spec[1]) if len(spec) > 1 else {}
    cfg = cls(num_heads=n_heads, **kwargs)
    uni = getattr(cfg, "attention", "bidirectional") == "unidirectional"
    return schedule_from_layout(
        cfg.make_layout(seq_len), cfg.block, causal=causal or uni,
        block_q=block or None, block_kv=block or None,
    )


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture config. Defaults give a Llama-style decoder."""

    vocab_size: int = 32000
    hidden_size: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None → MHA; < n_heads → GQA; 1 → MQA
    ffn_hidden_size: Optional[int] = None  # None → 4x (gelu) / 8/3x rounded (swiglu)
    max_seq_len: int = 2048
    norm: str = "rmsnorm"  # rmsnorm | rmsnorm_1p (gemma zero-centered) | layernorm
    # swiglu | geglu (gemma) | gelu (tanh) | gelu_exact (erf) | relu |
    # quick_gelu (CLIP: x * sigmoid(1.702 x))
    activation: str = "swiglu"
    position: str = "rope"  # rope | learned | alibi (bloom) | none
    rope_theta: float = 10000.0
    # Scaled RoPE (HF rope_scaling; reference AutoTP serves these checkpoints
    # via the wrapped HF module — module_inject/auto_tp.py:193 — so parity
    # requires native support): canonical hashable form, a sorted tuple of
    # (key, value) pairs with list values as tuples. Build it with
    # ``rope_scaling_from_hf``. Supported rope_type: linear, dynamic, yarn,
    # longrope, llama3. None → plain theta RoPE.
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- per-arch variations (reference module_inject/containers/ +
    # inference/v2/model_implementations/ breadth) -------------------------
    # decoupled head dim (mistral-nemo / qwen3 style): projections become
    # [h, n_heads*head_dim] with head_dim != h/n_heads
    head_dim_override: Optional[int] = None
    # q/k normalization over head_dim, applied to the head-reshaped
    # projections BEFORE rope. "rmsnorm" (qwen3): one [d] weight per layer
    # shared across heads. "layernorm_per_head" (stablelm-2 qk_layernorm):
    # biasless LayerNorm with PER-HEAD weights ([nh, d] / [nkv, d]).
    # "layernorm" (phi qk_layernorm): one affine LayerNorm ([d] weight +
    # bias) shared across heads. "rmsnorm_full" (olmoe / olmo2): one RMSNorm
    # over the WHOLE projection width ([nh*d] / [nkv*d] weights), applied
    # before the head reshape.
    qk_norm: bool = False
    qk_norm_kind: str = "rmsnorm"
    attn_qkv_bias: bool = False  # qwen2-style bias on q/k/v projections
    attn_out_bias: bool = False  # phi-style bias on the output projection
    mlp_bias: bool = False  # phi-style bias on MLP projections
    lm_head_bias: bool = False  # phi ships a biased lm_head
    # falcon/phi parallel block: x + attn(norm1(x)) + mlp(norm2(x)) — one
    # residual stream, attention and MLP branches computed from pre-attn
    # state (falcon-7b/phi share one norm: import the same weights into both)
    parallel_block: bool = False
    # phi partial rotary: rope applies to the first rope_frac*head_dim dims
    rope_frac: float = 1.0
    # softmax scale override: None → 1/sqrt(head_dim); gpt_neo uses 1.0
    # (HF GPTNeoSelfAttention never divides the logits)
    attn_scale: Optional[float] = None
    # --- encoder family (bert/distilbert — reference module_inject/
    # containers/{bert,distil_bert}.py serve these through kernel injection;
    # the training transformer kernel, csrc/transformer/, is BERT-shaped) ---
    # False → bidirectional self-attention (encoder)
    attn_causal: bool = True
    # "pre" (GPT/llama: norm before each block) | "post" (BERT: norm AFTER
    # each residual add — x = LN(x + attn(x)); x = LN(x + mlp(x))) | "out"
    # (exaone4 / k-exaone: norm on each block's OUTPUT, the block reads the raw
    # stream — x = x + N(attn(x)); x = x + N(mlp(x)); attn_norm / mlp_norm are
    # those two output norms)
    norm_scheme: str = "pre"
    # > 0: token-type (segment) embeddings added into the stem (BERT);
    # forward takes token_type_ids (defaults to all-zeros)
    type_vocab_size: int = 0
    # BERT has no final norm (the post-LN layers end normalized already)
    final_norm: bool = True
    # masked-LM head: dense[h,h] + activation + LN before the tied decoder
    # (+ per-vocab bias) — BertForMaskedLM's cls.predictions transform
    mlm_head: bool = False
    # sliding-window attention (mistral/starcoder2 sliding_window, gpt_neo
    # local attention): query i sees keys in (i - window, i]. 0 = full
    # causal. Applies to every layer unless attn_layer_pattern says which.
    sliding_window: int = 0
    # per-layer window flags for alternating local/global stacks (gpt_neo
    # attention_types): tuple of n_layers ints, 1 = windowed, 0 = global.
    # None with sliding_window > 0 → all layers windowed.
    attn_layer_pattern: Optional[Tuple[int, ...]] = None
    # exaone4 / k-exaone: rotary on the WINDOWED layers of attn_layer_pattern
    # only; a global layer attends with no position term
    rope_window_only: bool = False
    # gemma scales embeddings by sqrt(hidden_size) after lookup
    embed_scale: bool = False
    # bloom applies a LayerNorm to the embedding output
    # (word_embeddings_layernorm); params carry embed_norm/embed_norm_b
    embed_norm: bool = False
    # layer-projection matmul precision (VERDICT fp8 lever; ops/qmatmul.py):
    # "default" = model dtype; "fp8" = e4m3 tensor-scaled forward operands;
    # "int8" = symmetric int8 forward (native 2x MXU rate on v5e). Backward
    # stays full precision (straight-through vjp). Head/embed stay dense.
    matmul_precision: str = "default"
    dtype: str = "bfloat16"
    remat: bool = True
    # remat policy knob (reference activation_checkpointing config; VERDICT
    # asked for this to be tunable): see remat_policy() for the names
    remat_policy: str = "dots_with_no_batch_dims"
    # Pallas fused head+CE (ops/fused_ce.py): skip materializing [b*s, V]
    # logits. Takes effect on single-device TPU; multi-chip uses the sharded
    # dense head.
    fused_ce: bool = False
    # MoE (0 → dense). When n_experts > 0 the MLP becomes a top-k gated MoE
    # over the `expert` mesh axis (parallel/moe/).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # the reference's ``drop_tokens`` (TopKGate, sharded_moe.py:452). False:
    # every token reaches its top-k experts whatever the load, as the served
    # HF MoE models compute; on one device's experts that is the sorted,
    # grouped-matmul dispatch (parallel/moe/grouped.py), under expert
    # parallelism the einsum dispatch at the capacity that drops nothing.
    # moe_capacity_factor is then unused.
    moe_drop_tokens: bool = True
    moe_aux_loss_coef: float = 0.01
    # Residual-MoE (reference moe/layer.py:29 use_residual, arXiv 2201.05596):
    # out = expert_out·coef₀ + dense_mlp(x)·coef₁ with coef = softmax of a
    # learned [h, 2] projection per token
    moe_residual: bool = False
    # qwen2-moe shared expert: a dense expert of this ffn width runs on every
    # token, added as sigmoid(shared_gate(x))·shared_mlp(x) (0 → none)
    moe_shared_expert_dim: int = 0
    # renormalize top-k combine weights over surviving experts (mixtral /
    # qwen2 norm_topk_prob=True); False keeps raw softmax mass (qwen1.5-moe)
    moe_norm_topk_prob: bool = True
    # this chip's SHARE of each layer's experts (expert parallelism seen from
    # one chip): > n_experts is the published count. The router is that wide
    # and takes its top-k over all of them; n_experts are held here, those
    # numbered from moe_expert_shard * n_experts; a (token, expert) pair of an
    # expert held elsewhere is neither multiplied nor summed, and the partial
    # sum plus what every chip computes alike (the shared expert) goes on.
    # 0: every expert is held (the router is n_experts wide).
    moe_experts_total: int = 0
    moe_expert_shard: int = 0
    # deepseek-v3 / k-exaone expert block. moe_dense_lead: the first layers
    # have a dense MLP of ffn_dim and no experts; their MLP is stacked under
    # params["layers"]["lead"] and everything of the expert block under
    # params["layers"]["sparse"] on [n_layers - moe_dense_lead]. moe_expert_dim:
    # an expert's (and the shared expert's unit) width where it differs from
    # ffn_dim (0: ffn_dim). moe_score "sigmoid": scores sigmoid(x Wr), the
    # top-k CHOSEN on score + router_bias (the checkpoint's
    # e_score_correction_bias) and weighted by the score alone, renormalised,
    # times moe_routed_scale. moe_shared_gated False: the shared expert is
    # added as it is, with no sigmoid(shared_gate_proj(x)) in front
    moe_dense_lead: int = 0
    moe_expert_dim: int = 0
    moe_score: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_shared_gated: bool = True
    # deepseek-v3 / a.x-k1 GROUPED top-k: the router's experts lie in
    # moe_n_group groups of equal size, a token keeps its moe_topk_group best
    # groups and takes its top-k inside them (a token's experts then lie on
    # few chips of a deployment that gives a chip a group). A group's score:
    # its LARGEST expert score where the router has no selection bias
    # (DeepSeek-V2's group_limited_greedy), the sum of its two largest
    # score + bias where it has one (DeepseekV3TopkRouter). moe_router_bias
    # False: a sigmoid router without the selection bias (a.x-k1's
    # topk_method "none"); no router_bias parameter then
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_router_bias: bool = True
    # latent attention (DeepseekV3Attention, a.x-k1): kv_lora_rank > 0. A
    # token's keys and values are ONE vector shared by every head: the normed
    # latent [kv_lora_rank] and qk_rope_dim rotated key dims; a head's keys
    # (qk_nope_dim, beside the shared rotated dims) and values (v_head_dim)
    # are wkv_b of the latent. Queries go through a low-rank pair with a norm
    # between (q_lora_rank), or with q_lora_rank 0 through ONE projection wq
    # (kimi_linear). head_dim is qk_nope_dim + qk_rope_dim (the
    # softmax's 1/sqrt), rotary turns the qk_rope_dim dims alone, in
    # INTERLEAVED pairs (2i, 2i+1) where rope_interleave; with position "none"
    # nothing turns and the "rope" dims are plain dims every head shares.
    # Parameters: wq_a, q_a_norm, wq_b (or wq), wkv_a, kv_a_norm, wkv_b in place
    # of wq / wk / wv, wo [n_heads * v_head_dim, h]. The serving engine caches
    # the one vector a token (``latent_dim`` wide) and attends in the absorbed
    # form. In a hybrid stack (layer_kinds) the "full" layers are the latent ones.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # longcat_flash (LongcatFlashMLA's mla_scale_q_lora / mla_scale_kv_lora):
    # the heads' queries (behind wq_b, nope and rope dims alike) are multiplied
    # by latent_q_scale = sqrt(hidden / q_lora_rank) and the normed latent by
    # latent_kv_scale = sqrt(hidden / kv_lora_rank); the shared rotary key dims
    # are not. The cache holds the latent AFTER its scale. 1.0: none
    latent_q_scale: float = 1.0
    latent_kv_scale: float = 1.0
    # the eps of the two norms INSIDE a latent attention (q_a_norm, kv_a_norm)
    # where it is not norm_eps: LongcatFlashMLA builds them with its RMSNorm's
    # default, 1e-6, beside layer norms of rms_norm_eps 1e-5. None: norm_eps
    latent_norm_eps: Optional[float] = None
    # longcat_flash's layer (shortcut-connected experts): TWO sub-blocks, each a
    # latent attention and a dense MLP of ffn_dim on the stream, and ONE expert
    # block that reads the first sub-block's normed MLP input and joins the
    # stream behind the SECOND sub-block's MLP:
    #   h = x + A0(N(x)); m = N(h); s = E(m); h = h + D0(m)
    #   h = h + A1(N(h)); x' = h + D1(N(h)) + s
    # n_layers counts such layers. Parameters: the expert block on [n_layers]
    # as every expert model's (router, router_bias, w_up / w_gate / w_down
    # [n_layers, E, ...]), and what a sub-block has (attn_norm, mlp_norm, the
    # latent keys, wo, the dense MLP's w_up / w_gate / w_down) under
    # params["layers"]["sub"] on [2 * n_layers], sub-block i of layer l at
    # 2 l + i; the cache is as deep (``kv_layers``), plane 2 l + i its vector
    moe_shortcut: bool = False
    # longcat_flash's zero-computation experts: the router is moe_zero_experts
    # wider than the experts there are, and a chosen id behind them is the
    # IDENTITY: it adds gate x the expert block's input, has no weight, no row
    # in the grouped matmul and no chip (every chip adds it for its own tokens)
    moe_zero_experts: int = 0
    # mimo_v2_flash: a mixed stack (attn_layer_pattern with both flags) whose
    # window layers differ from its global ones in more than the window.
    # window_kv_heads > 0: the window layers have that many KV heads, the
    # global ones n_kv_heads, and the attention parameters are stacked BY KIND,
    # under params["layers"]["full"] on [kv_layers] and ["window"] on
    # [window_layers] (a window layer's wk / wv are of another shape: one
    # [n_layers, ...] stack cannot hold both; ``attn_by_kind``). Without
    # kv_lora_rank, v_head_dim > 0 is a per-head value width of its own (wv
    # [h, nkv * v_head_dim], wo [n_heads * v_head_dim, h]; ``value_dim``).
    # attn_sink_window: a window layer's softmax has a learned logit a head in
    # its denominator, which takes mass and adds no value (``sink`` [window
    # layers, n_heads], float32 where it is used). window_rope_theta > 0: the
    # rotary base of the window layers (rope_theta: the global ones').
    # attn_value_scale: values are multiplied by it before they are attended.
    window_kv_heads: int = 0
    attn_sink_window: bool = False
    window_rope_theta: float = 0.0
    attn_value_scale: float = 1.0
    # per-layer KIND (qwen3-next, jamba, kimi_linear): n_layers names, "full"
    # (softmax attention over cached keys and values) or ONE recurrent kind
    # (``RECURRENT``): "gdn" (Gated DeltaNet, ops/linear_attention), "mamba"
    # (a selective state-space layer, ops/state_space) or "kda" (Kimi Delta
    # Attention: the delta rule with a decay a key channel), each a recurrent
    # state and a short causal conv. None: every layer "full". Parameters are
    # stacked by kind: what every layer has (norms, MLP or experts) on
    # [n_layers] (under moe_dense_lead the MLPs under "lead" / "sparse"),
    # attention under params["layers"]["full"] on [number of full layers], the
    # recurrent kind's under params["layers"]["gdn"] / ["mamba"] / ["kda"].
    layer_kinds: Optional[Tuple[str, ...]] = None
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv_kernel: int = 4
    # a "mamba" layer (Mamba-1 as Jamba writes it): channels (expand x hidden),
    # numbers of state a channel, the rank of the step size's projection
    mamba_d_inner: int = 0
    mamba_d_state: int = 0
    mamba_dt_rank: int = 0
    mamba_conv_kernel: int = 4
    # a "kda" layer: heads of kda_head_dim keys and as many values; the decay's
    # and the output gate's low-rank pairs go through kda_head_dim too
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    # qwen3-next gated attention output: q_proj is twice as wide, per head a
    # query and a gate, and the heads' output is multiplied by sigmoid(gate)
    # in front of wo (stored apart as wq_gate [h, n_heads * head_dim])
    attn_out_gate: bool = False
    vocab_parallel: bool = True  # shard embedding/lm_head vocab dim on `model`
    # sequence-parallel attention: "ulysses" (all-to-all head scatter) or
    # "ring" (ppermute blockwise — O(s/N) per-device memory, unbounded SP
    # degree; no segment_ids support)
    seq_impl: str = "ulysses"
    # attention backend seam (ops.attention.core dispatch): "auto" picks the
    # flash ring when the mesh's `context` axis is >1, else the platform
    # best; "flash_ring" / "flash_head_sharded" / "flash" / "reference"
    # force a specific path (hard error when shapes/mesh don't support it);
    # "splash" routes through the scheduled block-sparse kernel
    # (ops/sparse_attention/splash_pallas.py) — masked kv blocks are never
    # scheduled, cost scales with mask density not s²
    attention_impl: str = "auto"
    # splash mask family as a hashable spec: (kind, ((kwarg, value), ...))
    # with kind ∈ "fixed" | "bigbird" | "bslongformer" | "variable" |
    # "dense" (the SparsityConfig families). The spec is compiled into a
    # compacted per-q-block schedule at trace time (a Python constant —
    # never rebuilt per step). None with attention_impl="splash" derives
    # the schedule from attn_causal/sliding_window instead.
    attn_sparsity: Optional[Tuple] = None
    # kernel block edge for splash schedules; 0 → the op-layer default
    # (DSTPU_SPLASH_BLOCK env or 512, shrunk to fit the sequence)
    splash_block: int = 0
    # >1: compute the LM loss per sequence tile so [b, s, vocab] logits never
    # materialize (ALST TiledFusedLogitsLoss, ulysses_sp.py:960) — frees
    # ~b*s*vocab bytes of activations at the cost of recomputing the head
    # matmul in backward (~1pp MFU at 32k vocab); enable when memory-bound
    loss_tiles: int = 0
    # quantized collectives seam (comm/quantized.py): "int8" moves the MoE
    # expert-parallel dispatch/combine exchange (and, via the pipe/serving
    # configs that read it, the pipeline activation sends and the serving TP
    # psum) as int8 payloads + fp32 block scales INSIDE the collective;
    # "none" keeps full-width GSPMD collectives (bit-identical to before)
    comm_quant: str = "none"
    # ZeRO-Infinity weight streaming (reference partition_parameters.py
    # remote_device + partitioned_param_coordinator prefetch): params rest in
    # pinned_host; each scan iteration stages ONE layer's weights into HBM
    # (XLA's latency-hiding scheduler overlaps the copy with compute — the
    # reference's prefetch_bucket_size machinery for free), remat re-stages
    # them in backward, and weight grads stream back to host via the staging
    # vjp. HBM then holds one layer + activations, so models far larger than
    # HBM train on one chip. Requires offload_param + a TPU backend; no-op
    # elsewhere.
    weight_stream: bool = False

    def __post_init__(self):
        if self.norm_scheme not in ("pre", "post", "out"):
            raise ValueError(f"norm_scheme={self.norm_scheme!r}: expected 'pre', 'post' or 'out'")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score={self.moe_score!r}: expected 'softmax' or 'sigmoid'")
        if self.moe_dense_lead and not (
                0 < self.moe_dense_lead < self.n_layers and self.n_experts > 0):
            raise ValueError(
                f"moe_dense_lead={self.moe_dense_lead}: the lead layers are some, not all, of "
                f"an expert model's {self.n_layers} layers")
        if self.moe_n_group > 1 and (
                self.router_width % self.moe_n_group
                or not 0 < self.moe_topk_group <= self.moe_n_group
                or self.moe_topk_group * (self.router_width // self.moe_n_group) < self.moe_top_k
                or self.moe_score != "sigmoid"):
            raise ValueError(
                f"moe_n_group={self.moe_n_group}, moe_topk_group={self.moe_topk_group}: the "
                f"{self.router_width} experts of a sigmoid router in equal groups, the kept "
                f"groups holding at least moe_top_k={self.moe_top_k} experts")
        if self.kv_lora_rank and not (
                min(self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim) > 0
                and self.q_lora_rank >= 0 and self.qk_rope_dim % 2 == 0
                and self.head_dim_override == self.qk_nope_dim + self.qk_rope_dim
                and self.position in ("rope", "none") and self.norm_scheme == "pre"
                and not (self.sliding_window or self.qk_norm
                         or self.attn_qkv_bias or self.attn_out_bias or self.attn_out_gate
                         or self.parallel_block or self.rope_frac != 1.0)
                and not (self.layer_kinds and self.moe_shortcut)):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs qk_nope_dim, an even qk_rope_dim "
                "and v_head_dim (q_lora_rank 0: one query projection), head_dim_override = "
                "qk_nope_dim + qk_rope_dim, rotary or no positions and pre-norm blocks, and "
                "composes with no window, q/k norm, bias, output gate or parallel block, nor "
                "as layer kinds with a layer of two sub-blocks (moe_shortcut)")
        if self.moe_shortcut and not (
                self.latent and self.n_experts > 0 and not self.moe_dense_lead
                and not self.moe_shared_expert_dim and not self.moe_residual
                and not self.moe_drop_tokens):
            raise ValueError(
                "moe_shortcut: a layer of two latent-attention sub-blocks with dense MLPs and "
                "one drop-free expert block across them; composes with no dense lead layer, "
                "shared or residual expert")
        if self.moe_zero_experts and (self.moe_drop_tokens or self.moe_n_group > 1
                                      or self.n_experts < 1):
            raise ValueError(
                f"moe_zero_experts={self.moe_zero_experts}: identity experts behind those of a "
                "drop-free router without groups (the grouped dispatch adds them)")
        if self.attn_by_kind and not (
                self.window_layers > 0 and self.n_heads % self.window_kv_heads == 0
                and self.layer_kinds is None and not self.latent
                and self.norm_scheme == "pre" and not self.parallel_block
                and not (self.qk_norm or self.attn_qkv_bias or self.attn_out_bias
                         or self.attn_out_gate)):
            raise ValueError(
                f"window_kv_heads={self.window_kv_heads}: the KV heads of the window layers of a "
                "stack that mixes them with global ones (attn_layer_pattern with both flags), "
                "a divisor of n_heads; composes with pre-norm blocks alone and with no "
                "layer_kinds, latent attention, q/k norm, bias or output gate")
        if (self.attn_sink_window or self.window_rope_theta) and not self.attn_by_kind:
            raise ValueError("attn_sink_window / window_rope_theta belong to window layers whose "
                             "attention is stacked by kind (window_kv_heads > 0)")
        if self.rope_window_only and (self.attn_layer_pattern is None or self.position != "rope"):
            raise ValueError("rope_window_only needs attn_layer_pattern (which layers have the "
                             "window) and position='rope'")
        if self.qk_norm_kind not in ("rmsnorm", "rmsnorm_full", "layernorm", "layernorm_per_head"):
            raise ValueError(
                f"qk_norm_kind={self.qk_norm_kind!r}: expected 'rmsnorm', "
                "'rmsnorm_full', 'layernorm' or 'layernorm_per_head'"
            )
        if self.position == "alibi" and (self.sliding_window > 0 or self.attn_scale is not None):
            # the alibi training branch rides the flash kernel's rank-1 bias
            # and takes no window/scale — silently ignoring them would train
            # full-context and then DECODE windowed (train/serve mismatch)
            raise ValueError(
                "alibi attention does not compose with sliding_window or "
                "attn_scale (no supported arch combines them)"
            )
        if self.seq_impl not in ("ulysses", "ring"):
            raise ValueError(
                f"seq_impl={self.seq_impl!r}: expected 'ulysses' or 'ring' "
                "(a typo would silently fall back to the wrong parallelism)"
            )
        if self.attention_impl not in (
            "auto", "flash", "flash_head_sharded", "flash_ring", "reference",
            "splash",
        ):
            raise ValueError(
                f"attention_impl={self.attention_impl!r}: expected 'auto', "
                "'flash', 'flash_head_sharded', 'flash_ring', 'reference' "
                "or 'splash'"
            )
        if self.attn_sparsity is not None:
            if self.attention_impl not in ("auto", "splash"):
                raise ValueError(
                    f"attn_sparsity set with attention_impl="
                    f"{self.attention_impl!r} — the sparsity schedule only "
                    "routes through 'splash' (or 'auto' promotion)"
                )
            kind = self.attn_sparsity[0] if self.attn_sparsity else None
            if kind not in _SPARSITY_KINDS:
                raise ValueError(
                    f"attn_sparsity kind {kind!r}: expected one of "
                    f"{sorted(_SPARSITY_KINDS)}"
                )
            if self.sliding_window > 0:
                raise ValueError(
                    "attn_sparsity and sliding_window are mutually "
                    "exclusive — the sparsity layout replaces the window "
                    "band (silently ignoring the window would train a "
                    "different mask than configured)"
                )
        if self.attention_impl == "splash" or self.attn_sparsity is not None:
            if self.attn_layer_pattern is not None:
                raise ValueError(
                    "splash attention does not compose with "
                    "attn_layer_pattern — the per-layer window flag is a "
                    "traced scalar inside the layer scan, but splash "
                    "schedules are trace-time constants"
                )
            if self.position == "alibi":
                raise ValueError(
                    "splash attention does not compose with alibi (the "
                    "scheduled kernel takes no positional bias)"
                )
        if self.attn_layer_pattern is not None:
            if self.sliding_window <= 0:
                raise ValueError(
                    "attn_layer_pattern set without sliding_window — the "
                    "pattern flags which layers use the window"
                )
            if len(self.attn_layer_pattern) != self.n_layers:
                raise ValueError(
                    f"attn_layer_pattern has {len(self.attn_layer_pattern)} "
                    f"entries for {self.n_layers} layers"
                )
        if self.layer_kinds is not None:
            recurrent = set(self.layer_kinds) - {"full"}
            if (recurrent - set(RECURRENT) or len(self.layer_kinds) != self.n_layers
                    or len(recurrent) > 1):
                raise ValueError(
                    f"layer_kinds={self.layer_kinds!r}: expected {self.n_layers} "
                    "names, each 'full' or ONE of 'gdn', 'mamba' and 'kda'"
                )
            if "kda" in self.layer_kinds and min(self.kda_heads, self.kda_head_dim) < 1:
                raise ValueError("a 'kda' layer needs kda_heads / kda_head_dim")
            if "mamba" in self.layer_kinds and min(
                    self.mamba_d_inner, self.mamba_d_state, self.mamba_dt_rank) < 1:
                raise ValueError(
                    "a 'mamba' layer needs mamba_d_inner / mamba_d_state / mamba_dt_rank")
            if "gdn" in self.layer_kinds and (
                min(self.gdn_key_heads, self.gdn_value_heads, self.gdn_key_dim,
                    self.gdn_value_dim) < 1
                or self.gdn_value_heads % self.gdn_key_heads
            ):
                raise ValueError(
                    "a 'gdn' layer needs gdn_key_heads / gdn_value_heads / gdn_key_dim / "
                    "gdn_value_dim, the value heads a multiple of the key heads"
                )
            if self.sliding_window or self.norm_scheme != "pre" or self.parallel_block:
                raise ValueError(
                    "layer_kinds composes with neither sliding_window, post-norm "
                    "nor parallel blocks (no architecture combines them)"
                )
        if self.moe_experts_total and (
            self.moe_experts_total % max(1, self.n_experts)
            or not 0 <= self.moe_expert_shard < self.moe_experts_total // max(1, self.n_experts)
        ):
            raise ValueError(
                f"moe_experts_total={self.moe_experts_total} is shared in shares of "
                f"n_experts={self.n_experts}: shard {self.moe_expert_shard} is not one of them"
            )
        if self.comm_quant not in ("none", "int8"):
            raise ValueError(
                f"comm_quant={self.comm_quant!r}: expected 'none' or 'int8' "
                "(a typo would silently serve full-width collectives)"
            )
        if self.matmul_precision not in ("default", "fp8", "int8", "int8_tensor"):
            raise ValueError(
                f"matmul_precision={self.matmul_precision!r}: expected "
                "'default', 'fp8', 'int8' (per-token/per-channel scales) or "
                "'int8_tensor' (legacy per-tensor scales)"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        if self.hidden_size % self.n_heads != 0:
            raise ValueError(f"hidden_size {self.hidden_size} not divisible by "
                             f"n_heads {self.n_heads}")
        return self.hidden_size // self.n_heads

    @property
    def latent(self) -> bool:
        """True where a token's keys and values are one latent vector."""
        return self.kv_lora_rank > 0

    @property
    def value_dim(self) -> int:
        """A head's value width: ``v_head_dim`` where it is set, else the keys'."""
        return self.v_head_dim or self.head_dim

    @property
    def attn_by_kind(self) -> bool:
        """True where window and global layers have attention parameters of
        their own shapes, stacked by kind (``window_kv_heads``)."""
        return self.window_kv_heads > 0

    def kv_heads_of(self, kind: str) -> int:
        """KV heads of the layers that cache as ``kind`` ("full" / "window")."""
        return self.window_kv_heads if kind == "window" and self.attn_by_kind else self.kv_heads

    def rope_theta_of(self, kind: str) -> float:
        """Rotary base of the layers that cache as ``kind``."""
        return float(self.window_rope_theta if kind == "window" and self.window_rope_theta
                     else self.rope_theta)

    @property
    def latent_dim(self) -> int:
        """What a latent model caches a token a layer: the normed latent and
        the rotated key dims every head shares."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def routed_experts(self) -> int:
        """Experts there are, held here or elsewhere: the published count."""
        return self.moe_experts_total or self.n_experts

    @property
    def router_width(self) -> int:
        """Ids the router chooses among: the published experts, then the
        identity ones (``moe_zero_experts``)."""
        return self.routed_experts + self.moe_zero_experts

    @property
    def router_has_bias(self) -> bool:
        """True where the router CHOOSES on score + ``router_bias`` (a sigmoid
        router unless ``moe_router_bias`` is off; a softmax router with identity
        experts, whose share of the choices the bias steers)."""
        return self.n_experts > 0 and self.moe_router_bias and (
            self.moe_score == "sigmoid" or self.moe_zero_experts > 0)

    @property
    def sub_blocks(self) -> int:
        """Attention sub-blocks a layer has: 2 under ``moe_shortcut``."""
        return 2 if self.moe_shortcut else 1

    @property
    def hybrid(self) -> bool:
        """True where the layers are of more than one kind (``layer_kinds``)."""
        return self.recurrent_kind is not None

    @property
    def recurrent_kind(self) -> Optional[str]:
        """The stack's recurrent layer kind ("gdn", "mamba" or "kda":
        ``RECURRENT`` describes it), None for a stack of attention layers alone."""
        for kind in RECURRENT:
            if self.layer_kinds is not None and kind in self.layer_kinds:
                return kind
        return None

    def kind_count(self, kind: str) -> int:
        if self.layer_kinds is None:
            return self.n_layers if kind == "full" else 0
        return self.layer_kinds.count(kind)

    @property
    def kv_layers(self) -> int:
        """Layers that cache the whole context's keys and values: the block
        pool's leading dimension (a mixed stack's window layers have a pool of
        their own, ``window_layers`` deep). A ``moe_shortcut`` layer caches a
        plane for each of its two sub-blocks."""
        return (self.kind_count("full") - self.window_layers) * self.sub_blocks

    @property
    def window_layers(self) -> int:
        """Layers that attend within ``sliding_window`` in a stack that also has
        global layers (``attn_layer_pattern`` with both flags): they cache a
        window of keys and values, not the context. 0 for a uniform stack."""
        p = self.attn_layer_pattern
        return sum(p) if p is not None and self.sliding_window and 0 < sum(p) < len(p) else 0

    @property
    def expert_dim(self) -> int:
        return self.moe_expert_dim or self.ffn_dim

    @property
    def gdn_conv_dim(self) -> int:
        """Channels of a DeltaNet layer's conv: q and k at the key heads, v."""
        return 2 * self.gdn_key_heads * self.gdn_key_dim + self.gdn_value_heads * self.gdn_value_dim

    @property
    def ffn_dim(self) -> int:
        if self.ffn_hidden_size:
            return self.ffn_hidden_size
        if self.activation in ("swiglu", "geglu"):
            # llama-style 2/3 * 4h rounded up to a multiple of 256
            d = int(8 * self.hidden_size / 3)
            return ((d + 255) // 256) * 256
        return 4 * self.hidden_size


# Presets roughly tracking the reference's benchmark targets (BASELINE.json).
PRESETS: Dict[str, Dict[str, Any]] = {
    "tiny": dict(vocab_size=512, hidden_size=128, n_layers=2, n_heads=4, max_seq_len=256),
    "gpt2-small": dict(
        vocab_size=50257, hidden_size=768, n_layers=12, n_heads=12, max_seq_len=1024,
        norm="layernorm", activation="gelu", position="learned", tie_embeddings=True,
    ),
    "llama-7b": dict(
        vocab_size=32000, hidden_size=4096, n_layers=32, n_heads=32, max_seq_len=4096,
        ffn_hidden_size=11008,
    ),
    "llama-1b": dict(
        vocab_size=32000, hidden_size=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        max_seq_len=4096, ffn_hidden_size=5632,
    ),
    # what chip_smoke.py trains and serves at start-up: a width one v5e holds
    # under ZeRO-3; d=128 heads, 3:1 GQA, 3x ffn
    "bench-767m": dict(
        vocab_size=32000, hidden_size=2304, n_layers=10, n_heads=18,
        n_kv_heads=6, ffn_hidden_size=6912, max_seq_len=2048,
        remat_policy="flash",
    ),
    "mixtral-tiny": dict(
        vocab_size=1024, hidden_size=256, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=512, n_experts=4, moe_top_k=2,
    ),
}


def get_config(preset: str = "tiny", **overrides) -> TransformerConfig:
    kw = dict(PRESETS[preset])
    kw.update(overrides)
    return TransformerConfig(**kw)


# the per-layer parameters of softmax attention: with layer_kinds they are
# stacked on the "full" layers alone, under params["layers"]["full"]
ATTENTION_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "wq_gate", "wq_b", "wk_b", "wv_b", "wo_b",
    "q_norm", "k_norm", "q_norm_b", "k_norm_b",
})
# what a latent-attention layer has in place of wq / wk / wv (and their biases:
# "wq_b" above is the q BIAS of a qwen2 layer, here the second query projection)
LATENT_KEYS = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b")


def latent_keys(c: TransformerConfig) -> Tuple[str, ...]:
    """``LATENT_KEYS`` as this model has them: with ``q_lora_rank`` 0 the
    queries are ONE projection, ``wq``, in place of the low-rank pair."""
    return LATENT_KEYS if c.q_lora_rank else ("wq",) + LATENT_KEYS[3:]


def layer_period(c: TransformerConfig) -> Tuple[Tuple[str, ...], int]:
    """(the kinds of one period, how many periods) of ``layer_kinds``: the
    shortest pattern the stack repeats, so a loop over periods with the
    period's layers unrolled in its body serves the stack with one traced
    body a kind."""
    kinds = c.layer_kinds or ("full",) * c.n_layers
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p], len(kinds) // p
    raise AssertionError("unreachable: the whole stack is a period")


def kind_ordinals(c: TransformerConfig) -> Tuple[int, ...]:
    """For each layer its ordinal among the layers of its kind: where it sits
    in its kind's parameter stack and in its kind's cache pool."""
    kinds = c.layer_kinds or ("full",) * c.n_layers
    return tuple(kinds[:i].count(k) for i, k in enumerate(kinds))


def layer_stacks(c: TransformerConfig, li: int) -> Tuple[Tuple[str, int], ...]:
    """The sub-stacks of ``params["layers"]`` that hold what layer ``li`` has
    beside the common keys, each with the layer's index in it: its kind's stack
    under ``layer_kinds``; "lead" / "sparse" under ``moe_dense_lead`` (its MLP)
    and "full" / "window" under ``attn_by_kind`` (its attention), a layer of
    a model with two of these having one of each; none where every layer is alike. ``li``
    is static."""
    out = []
    if c.hybrid:
        out.append((c.layer_kinds[li], kind_ordinals(c)[li]))
    if c.attn_by_kind:
        out.append((cache_kinds(c)[li], cache_ordinals(c)[li]))
    if c.moe_dense_lead:
        lead = c.moe_dense_lead
        out.append(("lead", li) if li < lead else ("sparse", li - lead))
    return tuple(out)


def take_layer(layers, c: TransformerConfig, li, take):
    """One layer's parameters out of the stacked tree: ``take(stack, index)``
    on what every layer has at ``li`` and on its sub-stacks (``layer_stacks``)
    at the layer's index there. ``li`` is static."""
    out = jax.tree.map(lambda a: take(a, li),
                       {k: v for k, v in layers.items() if not isinstance(v, dict)})
    for name, ki in layer_stacks(c, li):
        out.update(jax.tree.map(lambda a: take(a, ki), layers[name]))
    return out


def cache_kinds(c: TransformerConfig) -> Tuple[str, ...]:
    """What each layer caches for a served sequence: "full" (keys and values
    of the whole context, in the block pool), "window" (of the last
    ``sliding_window`` tokens, in the window pool: a mixed stack's windowed
    layers) or "gdn" (a recurrent state)."""
    if c.layer_kinds is not None:
        return c.layer_kinds
    if not c.window_layers:
        return ("full",) * c.n_layers
    return tuple("window" if f else "full" for f in c.attn_layer_pattern)


def cache_ordinals(c: TransformerConfig) -> Tuple[int, ...]:
    """For each layer its ordinal among the layers that cache alike: where it
    sits in its kind's cache pool."""
    kinds = cache_kinds(c)
    return tuple(kinds[:i].count(k) for i, k in enumerate(kinds))


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------
def init_params(config: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Initialize the parameter pytree. Layer weights are stacked on a leading
    [n_layers] dim for the scan-based forward."""
    c = config
    dtype = DTYPES[c.dtype]
    h, d, nh, nkv = c.hidden_size, c.head_dim, c.n_heads, c.kv_heads
    ffn = c.ffn_dim
    L = c.n_layers
    keys = iter(jax.random.split(key, 48))

    def dense(k, shape, fan_in, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * (gain / math.sqrt(fan_in))).astype(dtype)

    # Seeded weights stand in for a checkpoint wherever the served tokens are
    # held to a float32 reference. With the unit-gain draws below every
    # block's output is as large as the stream it is added to (the 0.02
    # embedding is gone after layer 0), which a model of matmuls and softmaxes
    # bears. A model that DECIDES does not: the top 10 of a 512-wide router,
    # renormalised, turn a rounding of their input into another choice of
    # experts, and one turned choice then moves all that follows (at the
    # published Qwen3-Next widths bf16 missed float32 by a whole logit). So a
    # hybrid model is seeded as a pre-norm model is trained from and ends up:
    # the stream starts at unit rms and the projections INTO it are drawn at
    # 1 / sqrt(2 layers) of the others (the GPT-2 / Megatron residual
    # scaling): a block adds a fraction of the stream, as in a checkpoint.
    # A model that norms each block's OUTPUT (norm_scheme "out") has the same
    # knob in another place: what a block adds is its output norm's weight
    # whatever the matrices' gain, so those weights are set small, to
    # 1 / (2 layers), and the matrices keep unit gain. (At 1 / sqrt(2 layers) a
    # block still adds a quarter of the stream at 8 layers, and K-EXAONE's
    # share of 16 of 128 sigmoid-routed experts, whose partial sum is what
    # the norm sees, turned enough top-8 choices in bf16 to put the served
    # tokens 0.09-0.27 under the float32 reference's best logit, limit 0.15,
    # with nothing wrong in the program. The weight scales a FAULT's reading
    # as it scales a rounding's, 15-18x apart at either weight: on the chip at
    # 1 / (2 layers) bf16 reads 0.04-0.09 and a window pool lost, rings not
    # wrapped or a window not applied 0.9-1.1; at 1 / sqrt(2 layers) 0.27 and
    # 3-5. The benchmark's fixed limit lies between the two at the smaller
    # weight only: PERF.md section 6, PR 31.)
    # A latent-attention model (a.x-k1) is seeded BY COMPONENT, so that the
    # comparison tells a fault in what is new (the latent cache, the absorbed
    # attention) from bf16, which one gain for everything could not (my chip
    # runs, PR 37: PERF.md section 6 has every reading). (1) Attention has to
    # ATTEND: at unit gain 64 heads' scores over thousands of keys have std 1.8,
    # a softmax spread over ~250 of 4,096 keys whose output is 7% of a value's
    # size, and with every projection into the stream at 1 / (2 layers) rotary
    # dropped from the cache, a neighbouring head's W_UK, a lost norm or the
    # wrong softmax scale all read under the limit (0.09-0.16 against bf16's
    # 0.06-0.09, limit 0.15). So the second query projection is drawn at TWICE
    # unit gain (~14 keys carry a query, as a trained head's few) and o_proj
    # at 0.5: a layer's attention adds a fifth of the stream, those faults
    # read 1.3-3.0 and the cache through float8 0.13-0.22, bf16 0.04-0.05. At
    # 0.6 bf16 read 0.05-0.11 over eight runs: too near the limit. (2) The
    # latent's norm weights are drawn in [0.5, 1.5] (at 1 a vector of unit rms
    # is its own norm, and a cache that stores the latent BEFORE its norm reads
    # sound). (3) What DECIDES stays small: a router that chooses groups and
    # then experts turns ~5% of its choices on a bf16 rounding, each turned
    # choice moves the token by one expert's output, and an expert's down
    # projection at 1 / (6 layers) keeps that under a third of the limit (the
    # dense and shared MLPs at 1 / (2 layers)). The price, said in PERF.md: a
    # router that IGNORES its groups differs from the sound one by an expert or
    # two a token, which is what bf16 does too, and reads under the limit at
    # any gain that keeps bf16 under it (the CPU tests hold the groups exactly).
    # A stack whose window and global layers differ in heads, rotary base and
    # sink (mimo_v2_flash, ``attn_by_kind``) is seeded by component as well, for
    # the same three reasons: its attention has to attend for a swapped base,
    # a dropped sink or a window not applied to show, and its 256-wide sigmoid
    # router decides. Its sinks are drawn where they MATTER: at q gain 2 a
    # window row's 128 scores have std 2 and their log-sum-exp is ~6.9, so a
    # logit in [4.5, 6.5] takes a tenth to two fifths of the row's mass.
    # A layer of two sub-blocks and an expert block on a shortcut (longcat_flash,
    # ``moe_shortcut``) is seeded by component too, at gains of its own, because
    # its published scales and its router's law change what a unit gain means
    # (PERF.md section 6, PR 45, has the chip readings). (1) Its queries are
    # multiplied by latent_q_scale (2) and its latent by latent_kv_scale (3.46)
    # behind norms that leave unit rms, so at unit gain a score has std 6: the
    # second query projection is drawn at 0.6 (std 3.6, A.X-K1's with its YaRN
    # factor) and o_proj at 0.5 / latent_kv_scale (the values are as much
    # larger): an attention adds a fifth of the stream. (2) Its gates are
    # 6 x softmax over 768, not renormalised: ~0.01 each, a twentieth of
    # A.X-K1's, so the experts keep unit gain, and the router is drawn at a
    # quarter of unit gain: a turned top-12 choice moves a token by one gate
    # times the expert's output or, for an identity expert, times the block's
    # input itself, ~1% of the stream. The eight dense MLPs add 1 / (2 x 8).
    # (3) Four double layers add to a bf16 stream twenty times, A.X-K1's five
    # layers ten: the rounding of the stream itself, which no block's gain
    # changes, read 0.047-0.091 under the reference's best logit at a head of
    # unit gain (limit 0.15; A.X-K1 0.04-0.05). A shortfall scales with the
    # logits, so the head is drawn at 0.8: noise and faults both read a fifth
    # lower, and the limit sits between them with the same room on either side.
    by_component = c.latent or c.attn_by_kind
    latent_gain = {"q": 2.0, "attn": 0.5, "mlp": 1.0 / (2 * L), "experts": 1.0 / (6 * L),
                   "router": 1.0}
    if c.moe_shortcut:
        latent_gain = {"q": 0.6, "attn": 0.5 / c.latent_kv_scale, "mlp": 1.0 / (4 * L),
                       "experts": 1.0, "router": 0.25, "head": 0.8}
    into_stream = (latent_gain["experts"] if by_component else 1.0 / math.sqrt(2 * L) if c.hybrid
                   else 1.0)
    unit_stream = c.hybrid or by_component or c.norm_scheme == "out"
    # A TIED head reads the embedding back. At unit rms a logit would be a sum
    # of h products of unit size (std 51 at 2,560: a bf16 rounding of it as
    # large as the benchmark's whole limit on a served token's shortfall), and
    # the token's OWN row, still a unit-rms part of the stream the blocks added
    # fractions to, would score 47 where every other row scores a unit normal:
    # the model would repeat its input whatever the layers compute, and no
    # fault in them could turn a served token. So a hybrid model with a tied
    # head (jamba) draws its embedding near h ** -0.5: logits of unit scale, the
    # token's own row a few units at most, and a stream that the blocks'
    # outputs make up, each still 1 / sqrt(2 layers) of unit gain. (It has no
    # router: nothing DECIDES on a rounding, which is what the unit stream is for.)
    # ... at 0.35 of h ** -0.5, the head's gain: a shortfall scales with the
    # logits, and 26 Mamba layers' worth of bf16 roundings read 0.10-0.13 under
    # the float32 reference's best logit at unit logits (limit 0.15) where a
    # norm, the conv's bias, D or a state lost read 5-7; at 0.4 bf16 read
    # 0.04-0.065 and rotary in the two attention layers 0.21-0.26 (my chip
    # runs, PR 53: PERF.md section 6): the limit sits between them at 0.35
    embed_std = 1.0 if unit_stream else 0.02
    if c.hybrid and c.tie_embeddings:
        embed_std = 0.35 * h ** -0.5

    # rmsnorm_1p's effective scale is (1 + w): identity init is ZEROS there
    norm_one = jnp.zeros if c.norm == "rmsnorm_1p" else jnp.ones
    block_norm = norm_one
    if c.norm_scheme == "out":
        def block_norm(shape, dt):
            return jnp.full(shape, 1.0 / (2 * L), dt)
    # attention weights live on the layers that attend: all of them, or with
    # layer_kinds the "full" ones (stacked apart, under layers["full"])
    La = c.kind_count("full")
    Ls = L * c.sub_blocks  # norms and latent attention: a set a sub-block
    layers: Dict[str, Any] = {
        "attn_norm": block_norm((Ls, h), dtype),
        "mlp_norm": block_norm((Ls, h), dtype),
    }
    if c.latent:
        rank, qr = c.kv_lora_rank, c.q_lora_rank
        Ll = La * c.sub_blocks  # the latent layers: every sub-block, or a hybrid stack's "full" ones
        if qr:
            layers.update(
                wq_a=dense(next(keys), (Ll, h, qr), h),
                q_a_norm=jnp.ones((Ll, qr), dtype),
                wq_b=dense(next(keys), (Ll, qr, nh * d), qr, latent_gain["q"]))
        else:  # ONE query projection (kimi_linear), at the gain of the pair's second
            layers["wq"] = dense(next(keys), (Ll, h, nh * d), h, latent_gain["q"])
        layers.update(
            # the latent and, behind it, the rotary key dims every head shares
            wkv_a=dense(next(keys), (Ll, h, c.latent_dim), h),
            kv_a_norm=jax.random.uniform(next(keys), (Ll, rank), jnp.float32, 0.5, 1.5).astype(dtype),
            # per head: qk_nope_dim key columns, then v_head_dim value columns
            wkv_b=dense(next(keys), (Ll, rank, nh * (c.qk_nope_dim + c.v_head_dim)), rank),
            wo=dense(next(keys), (Ll, nh * c.v_head_dim, h), nh * c.v_head_dim, latent_gain["attn"]),
        )
    def attention(n, nkv):
        """Per-head attention's parameters on ``n`` layers of ``nkv`` KV heads."""
        dv = c.value_dim
        out = dict(
            # (a mamba stack's two attention layers have to ATTEND, as (1) above
            # says of a.x-k1's: at unit gain over thousands of keys the softmax is
            # near uniform and rotary applied where the model has no positions read
            # 0.17 against bf16's own 0.10: my chip run, PR 53)
            wq=dense(next(keys), (n, h, nh * d), h,
                     latent_gain["q"] if c.attn_by_kind or c.recurrent_kind == "mamba" else 1.0),
            wk=dense(next(keys), (n, h, nkv * d), h),
            wv=dense(next(keys), (n, h, nkv * dv), h),
            wo=dense(next(keys), (n, nh * dv, h), nh * dv,
                     latent_gain["attn"] if c.attn_by_kind else into_stream),
        )
        if c.attn_out_gate:
            out["wq_gate"] = dense(next(keys), (n, h, nh * d), h)
        if c.attn_qkv_bias:
            out["wq_b"] = jnp.zeros((n, nh * d), dtype)
            out["wk_b"] = jnp.zeros((n, nkv * d), dtype)
            out["wv_b"] = jnp.zeros((n, nkv * dv), dtype)
        if c.qk_norm:
            if c.qk_norm_kind == "layernorm_per_head":
                out["q_norm"] = jnp.ones((n, nh, d), dtype)
                out["k_norm"] = jnp.ones((n, nkv, d), dtype)
            elif c.qk_norm_kind == "rmsnorm_full":
                out["q_norm"] = jnp.ones((n, nh * d), dtype)
                out["k_norm"] = jnp.ones((n, nkv * d), dtype)
            else:
                # under rmsnorm_1p the per-head norm is (1 + w) too (qk_norm_apply)
                qk_one = norm_one if c.qk_norm_kind == "rmsnorm" else jnp.ones
                out["q_norm"] = qk_one((n, d), dtype)
                out["k_norm"] = qk_one((n, d), dtype)
                if c.qk_norm_kind == "layernorm":
                    out["q_norm_b"] = jnp.zeros((n, d), dtype)
                    out["k_norm_b"] = jnp.zeros((n, d), dtype)
        if c.attn_out_bias:
            out["wo_b"] = jnp.zeros((n, h), dtype)
        return out

    if c.norm == "layernorm":
        layers["attn_norm_b"] = jnp.zeros((L, h), dtype)
        layers["mlp_norm_b"] = jnp.zeros((L, h), dtype)
    if c.attn_by_kind:
        # by kind: the global layers' stack, then the window layers' with its sinks
        layers["full"] = attention(c.kv_layers, nkv)
        layers["window"] = attention(c.window_layers, c.window_kv_heads)
        if c.attn_sink_window:
            layers["window"]["sink"] = jax.random.uniform(
                next(keys), (c.window_layers, nh), jnp.float32, 4.5, 6.5).astype(dtype)
    elif not c.latent:
        layers.update(attention(La, nkv))
    if c.hybrid:
        own = ATTENTION_KEYS | set(LATENT_KEYS)
        layers = {k: v for k, v in layers.items() if k not in own} | {
            "full": {k: v for k, v in layers.items() if k in own}}
    if c.recurrent_kind == "gdn":
        Lg, nv = c.kind_count("gdn"), c.gdn_value_heads
        vd = nv * c.gdn_value_dim
        # the gates as such layers are TRAINED from (the flash-linear-attention
        # library's GatedDeltaNet): a step dt log-uniform in [1e-3, 1e-1] and
        # dt_bias its inverse softplus, A uniform in (0, 16), so that a head
        # forgets over tens to hundreds of tokens. ``transformers`` draws the
        # same A beside a placeholder dt_bias of 1, under which eight heads in
        # nine decay their state to nothing within one token
        dt = jnp.exp(jax.random.uniform(
            next(keys), (Lg, nv), jnp.float32, math.log(1e-3), math.log(1e-1)))
        layers["gdn"] = {
            # q | k at the key heads | v at the value heads: the conv's channels
            "gdn_qkv": dense(next(keys), (Lg, h, c.gdn_conv_dim), h),
            "gdn_z": dense(next(keys), (Lg, h, vd), h),
            "gdn_ba": dense(next(keys), (Lg, h, 2 * nv), h),  # b | a
            "gdn_conv": dense(next(keys), (Lg, c.gdn_conv_kernel, c.gdn_conv_dim), c.gdn_conv_kernel),
            "gdn_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "gdn_a_log": jnp.log(jax.random.uniform(
                next(keys), (Lg, nv), jnp.float32, 1e-3, 16.0)).astype(dtype),
            "gdn_norm": jnp.ones((Lg, c.gdn_value_dim), dtype),
            "gdn_out": dense(next(keys), (Lg, vd, h), vd, into_stream),
        }
    if c.recurrent_kind == "mamba":
        Lm, di, N, R = c.kind_count("mamba"), c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
        # as such layers are TRAINED from (the Mamba code and ``transformers``'
        # JambaMambaMixer): A = -(1 .. N) a channel, D = 1, a step dt
        # log-uniform in [1e-3, 1e-1] with the step's bias its inverse softplus
        # and its projection at the std of uniform(+-rank ** -0.5), the conv's
        # weight and bias as torch draws a Conv1d's, uniform(+-K ** -0.5)
        dt = jnp.exp(jax.random.uniform(
            next(keys), (Lm, di), jnp.float32, math.log(1e-3), math.log(1e-1)))
        K = c.mamba_conv_kernel
        layers["mamba"] = {
            "mamba_in": dense(next(keys), (Lm, h, 2 * di), h),  # u | z
            "mamba_conv": jax.random.uniform(
                next(keys), (Lm, K, di), jnp.float32, -(K ** -0.5), K ** -0.5).astype(dtype),
            "mamba_conv_b": jax.random.uniform(
                next(keys), (Lm, di), jnp.float32, -(K ** -0.5), K ** -0.5).astype(dtype),
            "mamba_x": dense(next(keys), (Lm, di, R + 2 * N), di),  # dt | B | C
            "mamba_dt_norm": jnp.ones((Lm, R), dtype),
            "mamba_b_norm": jnp.ones((Lm, N), dtype),
            "mamba_c_norm": jnp.ones((Lm, N), dtype),
            "mamba_dt": dense(next(keys), (Lm, R, di), R, 3 ** -0.5),
            "mamba_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            # [N, d]: a state's numbers by rows, the channels on the lanes
            # (ops/state_space; a checkpoint's A_log is [d, N])
            "mamba_a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None], (Lm, N, di)
            ).astype(dtype),
            "mamba_d": jnp.ones((Lm, di), dtype),
            "mamba_out": dense(next(keys), (Lm, di, h), di, into_stream),
        }
    if c.recurrent_kind == "kda":
        Lk, H, dh = c.kind_count("kda"), c.kda_heads, c.kda_head_dim
        # (keys of their own: the 48 above are what every other model is seeded from)
        kk = iter(jax.random.split(jax.random.fold_in(key, 0x6B6461), 12))
        # THE DECAYS. A token multiplies channel c of head h by exp(g), g =
        # -exp(A_log[h]) * softplus(f(x)[h, c] + dt_bias[h, c]). A = exp(A_log)
        # uniform in (1, 16) a head, as the flash-linear-attention library draws
        # a delta rule's; dt_bias a CHANNEL the inverse softplus of 1 / (A tau),
        # with the channel's memory tau log-uniform in [10, 5,000] tokens: at
        # f = 0 channel c forgets over tau_c tokens, so every head has channels
        # that forget within ten tokens and channels that remember thousands
        # (a step log-uniform in [1e-3, 1e-1] under A up to 16, GDN's draw, lets
        # every channel forget within tens of tokens). f's low-rank pair at unit
        # gain moves a channel's tau by a factor of e either way with the input.
        A = jax.random.uniform(next(kk), (Lk, H), jnp.float32, 1.0, 16.0)
        tau = jnp.exp(jax.random.uniform(
            next(kk), (Lk, H, dh), jnp.float32, math.log(10.0), math.log(5000.0)))
        dt = 1.0 / (A[..., None] * tau)
        layers["kda"] = {
            # q | k | v, each [heads * head_dim]: the conv's channels
            "kda_qkv": dense(next(kk), (Lk, h, 3 * H * dh), h),
            "kda_conv": dense(next(kk), (Lk, c.kda_conv_kernel, 3 * H * dh), c.kda_conv_kernel),
            "kda_a_log": jnp.log(A).astype(dtype),
            "kda_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).reshape(Lk, H * dh).astype(dtype),
            "kda_f_a": dense(next(kk), (Lk, h, dh), h),
            "kda_f_b": dense(next(kk), (Lk, dh, H * dh), dh),
            "kda_b": dense(next(kk), (Lk, h, H), h),
            "kda_g_a": dense(next(kk), (Lk, h, dh), h),
            "kda_g_b": dense(next(kk), (Lk, dh, H * dh), dh),
            "kda_norm": jnp.ones((Lk, dh), dtype),
            "kda_out": dense(next(kk), (Lk, H * dh, h), H * dh, latent_gain["attn"]),
        }
    def dense_mlp(n):
        out = {"w_up": dense(next(keys), (n, h, ffn), h),
               "w_down": dense(next(keys), (n, ffn, h), ffn,
                               latent_gain["mlp"] if by_component else into_stream)}
        if c.activation in ("swiglu", "geglu"):
            out["w_gate"] = dense(next(keys), (n, h, ffn), h)
        return out

    if c.moe_shortcut:
        # what a sub-block has (its dense MLP too) under "sub" on [2 L]; the
        # expert block beside it on [L]
        layers.update(dense_mlp(Ls))
        layers = {"sub": layers}
    if c.n_experts > 0:
        E, ed = c.n_experts, c.expert_dim
        Le = L - c.moe_dense_lead  # the layers that have experts
        moe: Dict[str, Any] = {}
        moe["router"] = dense(next(keys), (Le, h, c.router_width), h, latent_gain["router"])
        moe["w_up"] = dense(next(keys), (Le, E, h, ed), h)
        moe["w_down"] = dense(next(keys), (Le, E, ed, h), ed, into_stream)
        if c.activation in ("swiglu", "geglu"):
            moe["w_gate"] = dense(next(keys), (Le, E, h, ed), h)
        if c.router_has_bias:
            # the selection bias: a checkpoint's is what balanced its experts'
            # load, a few hundredths of a sigmoid's score; beside a softmax
            # over router_width, as large as the spread of its probabilities
            # (std ~0.25 / width at the router's quarter gain)
            std = 0.02 if c.moe_score == "sigmoid" else 0.25 / c.router_width
            moe["router_bias"] = (jax.random.normal(
                next(keys), (Le, c.router_width), jnp.float32) * std).astype(dtype)
        if c.moe_residual:
            # dense residual expert + 2-way mixing coefficient (layer.py:47)
            moe["res_up"] = dense(next(keys), (Le, h, ffn), h)
            moe["res_down"] = dense(next(keys), (Le, ffn, h), ffn)
            if c.activation in ("swiglu", "geglu"):
                moe["res_gate"] = dense(next(keys), (Le, h, ffn), h)
            moe["res_coef"] = dense(next(keys), (Le, h, 2), h)
        if c.moe_shared_expert_dim > 0:
            sd = c.moe_shared_expert_dim
            moe["shared_up"] = dense(next(keys), (Le, h, sd), h)
            moe["shared_down"] = dense(next(keys), (Le, sd, h), sd,
                                       latent_gain["mlp"] if by_component else into_stream)
            if c.activation in ("swiglu", "geglu"):
                moe["shared_gate"] = dense(next(keys), (Le, h, sd), h)
            if c.moe_shared_gated:
                moe["shared_gate_proj"] = dense(next(keys), (Le, h, 1), h)
        if c.moe_dense_lead:
            layers["lead"] = dense_mlp(c.moe_dense_lead)
            layers["sparse"] = moe
        else:
            layers.update(moe)
    else:
        layers.update(dense_mlp(L))
    if c.mlp_bias and c.n_experts == 0:
        layers["w_up_b"] = jnp.zeros((L, ffn), dtype)
        layers["w_down_b"] = jnp.zeros((L, h), dtype)
        if c.activation in ("swiglu", "geglu"):
            layers["w_gate_b"] = jnp.zeros((L, ffn), dtype)

    params: Dict[str, Any] = {
        "embed": (jax.random.normal(next(keys), (c.vocab_size, h), jnp.float32)
                  * embed_std).astype(dtype),
        "layers": layers,
    }
    if c.final_norm:
        params["final_norm"] = norm_one((h,), dtype)
        if c.norm == "layernorm":
            params["final_norm_b"] = jnp.zeros((h,), dtype)
    if c.position == "learned":
        params["pos_embed"] = (
            jax.random.normal(next(keys), (c.max_seq_len, h), jnp.float32) * 0.02
        ).astype(dtype)
    if c.type_vocab_size > 0:
        params["type_embed"] = (
            jax.random.normal(next(keys), (c.type_vocab_size, h), jnp.float32) * 0.02
        ).astype(dtype)
    if c.embed_norm:
        params["embed_norm"] = jnp.ones((h,), dtype)
        params["embed_norm_b"] = jnp.zeros((h,), dtype)
    if c.mlm_head:
        params["mlm_dense"] = dense(next(keys), (h, h), h)
        params["mlm_dense_b"] = jnp.zeros((h,), dtype)
        params["mlm_norm"] = jnp.ones((h,), dtype)
        params["mlm_norm_b"] = jnp.zeros((h,), dtype)
        params["mlm_bias"] = jnp.zeros((c.vocab_size,), dtype)
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (h, c.vocab_size), h, latent_gain.get("head", 1.0))
        if c.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((c.vocab_size,), dtype)
    return params


def param_partition_specs(config: TransformerConfig) -> Dict[str, Any]:
    """Tensor-parallel PartitionSpecs (the declarative AutoTP): Megatron
    column/row sharding over the ``model`` axis. Leading layer-stack dim is
    never sharded. ZeRO later adds the ``data`` axis on free dims
    (runtime/zero/partition.py choose_zero_spec)."""
    c = config
    if (c.hybrid or c.attn_out_gate or c.moe_experts_total or c.latent or c.attn_by_kind
            or c.moe_zero_experts):
        raise NotImplementedError(
            "tensor-parallel partition specs for layer_kinds / attn_out_gate / an "
            "expert share / latent attention / attention stacked by kind: no sharded form "
            "of these has a test yet")
    m = MODEL_AXIS
    layers: Dict[str, Any] = {
        "attn_norm": P(None, None),
        "wq": P(None, None, m),  # column-parallel: shard heads
        "wk": P(None, None, m),
        "wv": P(None, None, m),
        "wo": P(None, m, None),  # row-parallel
        "mlp_norm": P(None, None),
    }
    if c.norm == "layernorm":
        layers["attn_norm_b"] = P(None, None)
        layers["mlp_norm_b"] = P(None, None)
    if c.attn_qkv_bias:
        # column-parallel biases shard with the output dim
        layers["wq_b"] = P(None, m)
        layers["wk_b"] = P(None, m)
        layers["wv_b"] = P(None, m)
    if c.qk_norm:
        if c.qk_norm_kind == "layernorm_per_head":
            # per-head weights shard with the heads (column-parallel q/k)
            layers["q_norm"] = P(None, m, None)
            layers["k_norm"] = P(None, m, None)
        elif c.qk_norm_kind == "rmsnorm_full":
            # one weight a projection column: sharded as the columns are
            layers["q_norm"] = P(None, m)
            layers["k_norm"] = P(None, m)
        else:
            # head-count-free [d] weights: replicated
            layers["q_norm"] = P(None, None)
            layers["k_norm"] = P(None, None)
            if c.qk_norm_kind == "layernorm":
                layers["q_norm_b"] = P(None, None)
                layers["k_norm_b"] = P(None, None)
    if c.attn_out_bias:
        layers["wo_b"] = P(None, None)  # row-parallel bias: replicated
    if c.n_experts > 0:
        from deepspeed_tpu.parallel.topology import EXPERT_AXIS

        e = EXPERT_AXIS
        layers["router"] = P(None, None, None)
        layers["w_up"] = P(None, e, None, m)
        layers["w_down"] = P(None, e, m, None)
        if c.activation in ("swiglu", "geglu"):
            layers["w_gate"] = P(None, e, None, m)
        if c.moe_residual:
            layers["res_up"] = P(None, None, m)
            layers["res_down"] = P(None, m, None)
            if c.activation in ("swiglu", "geglu"):
                layers["res_gate"] = P(None, None, m)
            layers["res_coef"] = P(None, None, None)
        if c.moe_shared_expert_dim > 0:
            layers["shared_up"] = P(None, None, m)
            layers["shared_down"] = P(None, m, None)
            if c.activation in ("swiglu", "geglu"):
                layers["shared_gate"] = P(None, None, m)
            layers["shared_gate_proj"] = P(None, None, None)
    else:
        layers["w_up"] = P(None, None, m)
        layers["w_down"] = P(None, m, None)
        if c.activation in ("swiglu", "geglu"):
            layers["w_gate"] = P(None, None, m)
    if c.mlp_bias and c.n_experts == 0:
        layers["w_up_b"] = P(None, m)
        layers["w_down_b"] = P(None, None)
        if c.activation in ("swiglu", "geglu"):
            layers["w_gate_b"] = P(None, m)

    vocab_spec = P(m, None) if c.vocab_parallel else P(None, None)
    specs: Dict[str, Any] = {
        "embed": vocab_spec,
        "layers": layers,
    }
    if c.final_norm:
        specs["final_norm"] = P(None)
        if c.norm == "layernorm":
            specs["final_norm_b"] = P(None)
    if c.position == "learned":
        specs["pos_embed"] = P(None, None)
    if c.type_vocab_size > 0:
        specs["type_embed"] = P(None, None)
    if c.embed_norm:
        specs["embed_norm"] = P(None)
        specs["embed_norm_b"] = P(None)
    if c.mlm_head:
        specs["mlm_dense"] = P(None, None)
        specs["mlm_dense_b"] = P(None)
        specs["mlm_norm"] = P(None)
        specs["mlm_norm_b"] = P(None)
        specs["mlm_bias"] = P(m) if c.vocab_parallel else P(None)
    if not c.tie_embeddings:
        specs["lm_head"] = P(None, m) if c.vocab_parallel else P(None, None)
        if c.lm_head_bias:
            specs["lm_head_b"] = P(m) if c.vocab_parallel else P(None)
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def remat_policy(name: str):
    """Map a config name to a ``jax.checkpoint`` policy: what a remat'd layer
    keeps for its backward. The four ``jax.checkpoint`` sites that take a
    policy (two in ``forward_hidden``, two in ``runtime.pipe.pipeline``) all
    take it from here. Least memory (most recomputed) first:

    * ``nothing``: the layer's input alone; the backward runs the whole layer
      again, the attention kernel's forward included.
    * ``flash``: only the attention kernel's output and log-sum-exp (tagged
      ``flash_out`` / ``flash_lse`` in ``ops.attention.flash_pallas._flash_fwd``,
      ``sharded`` and ``splash_pallas``): ``b·nh·s·(d·2 + 4)`` bytes a layer in
      bf16 (16 MiB + 256 KiB a 4,096-token sequence at 16 heads of 128). The
      backward re-runs every matrix product but never the kernel's forward.
    * ``flash_qkv``: also the rope'd q / k / v that feed the kernel, so the
      backward skips the three projections and the rope too.
    * ``dots_with_no_batch_dims`` (the default) and ``dots``: the output of
      every matrix product of the layer (q, k, v, ``wo``, gate, up, down: ~10
      activations of q's width) AND the kernel's ``flash_out`` / ``flash_lse``.
      The kernel is a ``pallas_call``, not a product: a policy of products
      alone drops its result and the backward of every layer runs the flash
      forward a second time to rebuild it. Keeping it costs one more
      activation of q's width a layer; a layer whose attention is plain XLA
      carries no tag and keeps what it kept.
    * ``everything``: no recompute; remat becomes a no-op barrier.

    A job that no longer fits takes ``flash``, ``nothing`` or a smaller
    micro-batch."""
    cp = jax.checkpoint_policies
    flash = cp.save_only_these_names("flash_out", "flash_lse")
    policies = {
        "nothing": cp.nothing_saveable,
        "flash": flash,
        "flash_qkv": cp.save_only_these_names("flash_out", "flash_lse", "flash_qkv"),
        "dots_with_no_batch_dims": cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, flash),
        "dots": cp.save_from_both_policies(cp.dots_saveable, flash),
        "everything": cp.everything_saveable,
    }
    if name not in policies:
        raise ValueError(f"remat_policy must be one of {sorted(policies)}, got {name!r}")
    return policies[name]


# ---------------------------------------------------------------------------
# weight streaming (ZeRO-Infinity tier)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _stage_to_device(w):
    """pinned_host → HBM copy whose cotangent flows back to host, so weight
    gradients of streamed layers accumulate in host memory, never HBM."""
    return jax.device_put(w, jax.memory.Space.Device)


def _stage_fwd(w):
    return _stage_to_device(w), None


def _stage_bwd(_, g):
    import os

    if os.environ.get("DSTPU_STREAM_GRADS_DEVICE", "0") == "1":
        # debug/bisect knob: leave weight grads in HBM (needs grads to fit)
        return (g,)
    return (jax.device_put(g, jax.memory.Space.Host),)


_stage_to_device.defvjp(_stage_fwd, _stage_bwd)


def _stream_active(c: TransformerConfig) -> bool:
    return c.weight_stream and on_tpu()


# ---------------------------------------------------------------------------
# bucketed parameter prefetch (ZeRO-3 comm/compute overlap)
# ---------------------------------------------------------------------------
# Scan-chunk width for ``forward_hidden``: with chunk B > 1 the layer scan
# runs over L/B chunks of B layers each, the inner B layers unrolled in the
# scan body. Layer b+1's parameter all-gather (ZeRO-3 GSPMD) or
# pinned_host→HBM stage (weight_stream) is data-independent of layer b's
# output, so inside ONE body the latency-hiding scheduler overlaps
# collective(b+1) with compute(b) — impossible across sequential scan
# iterations, where iteration i+1's HLO only exists after iteration i
# completes. B=2 is the two-slot double buffer; the engine sizes B from
# ``stage3_prefetch_bucket_size`` (runtime/zero/overlap.py overlap_chunk).
# Set via the ``overlap_scan`` context manager around TRACING (the engine
# wraps its loss calls); read once at trace time, so compiled steps keep the
# chunking they were traced with.
_OVERLAP_SCAN_CHUNK = 1


@contextmanager
def overlap_scan(chunk_layers: int):
    """Trace-scoped layer-scan chunking for comm/compute overlap."""
    global _OVERLAP_SCAN_CHUNK
    prev = _OVERLAP_SCAN_CHUNK
    _OVERLAP_SCAN_CHUNK = max(1, int(chunk_layers))
    try:
        yield
    finally:
        _OVERLAP_SCAN_CHUNK = prev


def _maybe_stage(w):
    """Stage only leaves that actually live in host memory (the engine keeps
    small leaves — norm vectors, biases — device-resident: their [1, h] scan
    slices violate libtpu's >=8-sublane host-DUS requirement, and at a few
    hundred KB they cost nothing in HBM)."""
    try:
        space = jax.typeof(w).memory_space
    except Exception:
        return _stage_to_device(w)
    return _stage_to_device(w) if space == jax.memory.Space.Host else w


def _stage_tree(tree):
    return jax.tree.map(_maybe_stage, tree)


def _norm(x, w, b, kind, eps):
    """Delegates to the ops layer (single definition; Pallas on TPU)."""
    from deepspeed_tpu.ops.normalization import fused_layer_norm, rms_norm

    if kind == "rmsnorm":
        y = rms_norm(x, w, eps)
        return y + b if b is not None else y
    if kind == "rmsnorm_1p":
        # gemma zero-centered weight: y = rms(x) * (1 + w), with the add in
        # fp32 (HF casts to float for it); the kernel accepts an fp32 weight
        y = rms_norm(x, 1.0 + w.astype(jnp.float32), eps)
        return y + b if b is not None else y
    return fused_layer_norm(x, w, b if b is not None else jnp.zeros_like(w), eps)


def rope_scaling_from_hf(scaling, original_max_position_embeddings=None):
    """HF ``rope_scaling`` dict → the canonical hashable config form.

    Returns None for absent/default scaling. ``original_max_position_
    embeddings`` is the TOP-LEVEL HF config field (phi3 longrope keeps the
    pretraining length there, not in the dict — modeling_rope_utils reads
    ``config.original_max_position_embeddings``); when given it is folded
    into the canonical dict so one structure carries all parameters.
    """
    if not scaling:
        return None
    if not isinstance(scaling, dict):
        raise ValueError(f"unsupported rope_scaling={scaling!r} (expected a dict)")
    kind = scaling.get("rope_type", scaling.get("type", "default"))
    if kind == "default":
        return None
    if kind not in ("linear", "dynamic", "yarn", "longrope", "llama3"):
        raise ValueError(
            f"unsupported rope_scaling type {kind!r}; supported: "
            "linear, dynamic, yarn, longrope, llama3"
        )
    out = {"rope_type": kind}
    for k, v in scaling.items():
        if k in ("rope_type", "type"):
            continue
        out[k] = tuple(float(x) for x in v) if isinstance(v, (list, tuple)) else v
    if kind == "longrope" and original_max_position_embeddings:
        # the top-level field wins (HF ignores any in-dict copy here)
        out["original_max_position_embeddings"] = int(original_max_position_embeddings)
    return tuple(sorted(out.items()))


def rope_params(c: "TransformerConfig", rot: int, seq_len: Optional[Any] = None,
                theta: Optional[float] = None):
    """Inverse frequencies [rot//2] + cos/sin attention factor.

    Faithful to HF ``modeling_rope_utils`` so scaled-RoPE checkpoints
    (llama-3.x, yarn, longrope/phi3, linear, dynamic-NTK) produce identical
    logits. ``seq_len`` plays HF's dynamic ``max(position_ids)+1`` role
    (longrope long/short-factor switch, dynamic-NTK growth) and may be a
    TRACED scalar — decode paths pass the live cache length, so the factor
    choice tracks the actual sequence, not the cache capacity. Seq-dependent
    kinds return a jnp inv_freq; the rest return static numpy (one HF
    divergence, deliberate: HF's dynamic-NTK ratchets to the longest length
    seen between resets, ours is per-call — identical on monotonic decode).
    """
    theta = float(c.rope_theta if theta is None else theta)  # (a layer kind's own base)
    dims = np.arange(0, rot, 2, dtype=np.float32) / rot
    inv_freq = 1.0 / (theta**dims)
    sc = dict(c.rope_scaling) if c.rope_scaling else None
    if not sc:
        return inv_freq.astype(np.float32), 1.0
    kind = sc["rope_type"]
    factor = float(sc.get("factor", 1.0))
    if kind == "linear":
        return (inv_freq / factor).astype(np.float32), 1.0
    if kind == "dynamic":
        maxp = c.max_seq_len
        seq = jnp.maximum(jnp.asarray(seq_len if seq_len is not None else maxp, jnp.float32), maxp)
        base = theta * ((factor * seq / maxp) - (factor - 1)) ** (rot / (rot - 2))
        return 1.0 / (base ** jnp.asarray(dims)), 1.0
    if kind == "llama3":
        old_len = float(sc["original_max_position_embeddings"])
        low_wl = old_len / float(sc["low_freq_factor"])
        high_wl = old_len / float(sc["high_freq_factor"])
        wavelen = 2 * math.pi / inv_freq
        scaled = np.where(wavelen > low_wl, inv_freq / factor, inv_freq)
        smooth = (old_len / wavelen - float(sc["low_freq_factor"])) / (
            float(sc["high_freq_factor"]) - float(sc["low_freq_factor"])
        )
        mid = (1 - smooth) * scaled / factor + smooth * scaled
        is_mid = (wavelen >= high_wl) & (wavelen <= low_wl)
        return np.where(is_mid, mid, scaled).astype(np.float32), 1.0
    if kind == "yarn":
        old_len = float(sc.get("original_max_position_embeddings") or c.max_seq_len)
        attn = sc.get("attention_factor")
        mscale, mscale_all = sc.get("mscale"), sc.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
            return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

        if attn is None:
            attn = (
                get_mscale(factor, mscale) / get_mscale(factor, mscale_all)
                if mscale and mscale_all
                else get_mscale(factor)
            )
        beta_fast = float(sc.get("beta_fast") or 32)
        beta_slow = float(sc.get("beta_slow") or 1)

        def corr_dim(n_rot):
            return rot * math.log(old_len / (n_rot * 2 * math.pi)) / (2 * math.log(theta))

        low, high = corr_dim(beta_fast), corr_dim(beta_slow)
        if sc.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2, dtype=np.float32) - low) / (high - low), 0, 1)
        extrap = 1.0 - ramp  # 1 → keep base freq, 0 → interpolate by factor
        return (
            (inv_freq / factor * (1 - extrap) + inv_freq * extrap).astype(np.float32),
            float(attn),
        )
    # longrope: per-dim factor lists, chosen by seq length vs pretrain length
    old_len = int(sc.get("original_max_position_embeddings") or c.max_seq_len)
    if factor == 1.0 and sc.get("original_max_position_embeddings"):
        factor = c.max_seq_len / old_len
    attn = sc.get("attention_factor")
    if attn is None:
        attn = 1.0 if factor <= 1.0 else math.sqrt(1 + math.log(factor) / math.log(old_len))
    short = 1.0 / (np.asarray(sc["short_factor"], np.float32) * theta**dims)
    long = 1.0 / (np.asarray(sc["long_factor"], np.float32) * theta**dims)
    if seq_len is None or isinstance(seq_len, (int, float)):
        return (long if (seq_len or 0) > old_len else short).astype(np.float32), float(attn)
    return jnp.where(seq_len > old_len, jnp.asarray(long), jnp.asarray(short)), float(attn)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (HF ``build_alibi_tensor`` formula, incl. the
    non-power-of-2 interpolation). Returns fp32 [n_heads]."""
    closest = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = base ** np.arange(1, closest + 1, dtype=np.float32)
    if closest != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        extra = extra_base ** np.arange(1, 2 * (n_heads - closest) + 1, 2, dtype=np.float32)
        slopes = np.concatenate([slopes, extra])
    return slopes.astype(np.float32)


def _alibi_bias(c: TransformerConfig, key_positions: jax.Array) -> jax.Array:
    """ALiBi attention bias ``slope_h * key_position`` → [1|b, nh, 1, sk].

    HF bloom biases by the ABSOLUTE key position (cumsum(mask)-1 == arange);
    with a causal mask this equals the relative form up to a per-row constant
    the softmax cancels — matching HF exactly keeps logits bit-comparable."""
    slopes = jnp.asarray(alibi_slopes(c.n_heads))
    if key_positions.ndim == 1:
        key_positions = key_positions[None]
    return slopes[None, :, None, None] * key_positions[:, None, None, :].astype(jnp.float32)


def _rope(
    x: jax.Array,
    positions: jax.Array,
    c: "TransformerConfig",
    seq_len: Optional[Any] = None,
    theta: Optional[float] = None,
    head_axis: int = 1,
) -> jax.Array:
    """Rotary embedding on [b, h, s, d] given positions [b, s] or [s]
    (head-major: ``head_axis`` 1, what the serving steps and the v1 decode
    pass), or on x with its tokens laid as ``positions`` lays them and the
    heads at ``head_axis``: [b, s, h, d] with [b, s], or the tiles' view
    [b, s / 8, h, 8, d] with [b, s / 8, 8] (``ops.attention.heads_view``,
    ``head_axis`` 2: where the projections wrote it). cos and sin are a
    position's, broadcast over the head axis where it is: the same float32
    arithmetic on the same numbers whichever.
    ``theta``: the base where the layer's kind has its own (``rope_theta_of``).

    rope_frac < 1 (phi partial rotary, HF partial_rotary_factor): only the
    first ``frac*d`` dims rotate; the tail passes through unrotated.
    Scaled RoPE (config.rope_scaling) adjusts the frequencies and cos/sin
    magnitude per ``rope_params``; ``seq_len`` (static or traced) feeds its
    longrope/dynamic-NTK length dependence."""
    d = x.shape[-1]
    frac = c.rope_frac
    rot = d if frac >= 1.0 else (int(d * frac) // 2) * 2
    tail = None
    if rot < d:
        x, tail = x[..., :rot], x[..., rot:]
    inv_freq, attn_factor = rope_params(c, rot, seq_len, theta)
    freqs = jnp.asarray(inv_freq)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b, s, rot/2]
    # attn_factor scales cos/sin directly (HF convention: yarn/longrope
    # "attention_scaling" multiplies the embedding, hence scores by factor²)
    over_heads = (slice(None),) * head_axis + (None,)
    cos = jnp.cos(angles)[over_heads] * attn_factor  # [b, 1, s, rot/2] head-major
    sin = jnp.sin(angles)[over_heads] * attn_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(tail.dtype if tail is not None else x.dtype)
    return out if tail is None else jnp.concatenate([out, tail], axis=-1)


def _scale_embed(x, c: TransformerConfig, dtype):
    """gemma sqrt(h) embedding normalizer — HF rounds it to the model dtype
    BEFORE the multiply, so match that exactly (one definition for every
    embed-lookup site)."""
    if not c.embed_scale:
        return x
    return x * jnp.asarray(math.sqrt(c.hidden_size), dtype)


def _act_constraint(x, seq_sharded=True):
    """Sharding constraint for [b, s, h] activations. The sequence dim
    shards over ``context`` (ring — every layer op outside attention is
    pointwise over s, so per-device activations stay O(s/N) end to end)
    and/or ``sequence`` (Ulysses)."""
    topo = get_topology()
    axes = []
    if seq_sharded and topo.context_parallel_size > 1:
        axes.append(CONTEXT_AXIS)
    if seq_sharded and topo.sequence_parallel_size > 1:
        axes.append(SEQUENCE_AXIS)
    seq = tuple(axes) if len(axes) > 1 else (axes[0] if axes else None)
    return constrain(x, BATCH_AXES, seq, None)


def _proj(c: TransformerConfig, x, w):
    """Layer projection honoring matmul_precision (quantized forward,
    full-precision backward — ops/qmatmul.py)."""
    from deepspeed_tpu.ops.stack_matmul import Stacked, stack_dot

    if c.matmul_precision == "default":
        return stack_dot(x, w)  # a served layer's weight may be its stack, unsliced
    from deepspeed_tpu.ops.qmatmul import qmatmul

    return qmatmul(x, w.stack[w.index] if isinstance(w, Stacked) else w, c.matmul_precision)


def as_written(y):
    """A product's output (or a tuple of them) that is about to be split by
    heads, pinned as the ``[rows, n]`` matrix the product wrote. Unpinned, the
    TPU's compiler carries the heads-major layout back through the product,
    batches it over heads and writes the layer's weight out of its looped stack
    transposed, every layer of every step; pinned, it reads the stack in place
    as every other projection's product does. An identity, differentiable. Held
    by the ``qwen3_*`` and ``a_x_k1_*`` cases of tests/unit/ops/
    test_latent_attention.py::test_mimo_v2_flashs_split_step_compiles_for_a_v5e_with_no_pool_sized_copy."""
    return jax.lax.optimization_barrier(y)


def qk_norm_apply(c: TransformerConfig, x, w, head_axis: int, b=None):
    """THE q/k-norm application, shared by the training/decode attention
    block and both v2 paged layer bodies. x: [..., d] with a head axis at
    ``head_axis``; w: [d] (qwen3 rmsnorm / phi affine layernorm, shared
    across heads) or [n_heads, d] (stablelm-2 biasless per-head LayerNorm);
    ``b``: [d] bias for the phi form. The olmoe form ("rmsnorm_full") norms
    the whole projection width, so its callers apply it BEFORE the head
    reshape (``qk_norm_full``): x [..., n_heads*d], w [n_heads*d]."""
    if c.qk_norm_kind in ("rmsnorm", "rmsnorm_full"):
        from deepspeed_tpu.ops.normalization.fused_norm import rms_norm_reference

        if c.norm == "rmsnorm_1p" and c.qk_norm_kind == "rmsnorm":
            w = 1.0 + w.astype(jnp.float32)  # zero-centred, as the layer norms (qwen3-next)
        return rms_norm_reference(x, w, c.norm_eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + c.norm_eps)
    if c.qk_norm_kind == "layernorm":  # shared affine (phi)
        y = y * w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        return y.astype(x.dtype)
    shape = [1] * x.ndim
    shape[head_axis] = w.shape[0]
    shape[-1] = w.shape[1]
    return (y * w.astype(jnp.float32).reshape(shape)).astype(x.dtype)


def qk_norm_full(c: TransformerConfig) -> bool:
    """True where the q/k norm spans the whole projection width and so runs
    before the head reshape; the per-head kinds run after it."""
    return c.qk_norm and c.qk_norm_kind == "rmsnorm_full"


def _window_bias(c: TransformerConfig, q_glob, k_pos, local_flag):
    """[sq, sk] fp32 additive bias masking keys ≥ sliding_window behind the
    query (band convention shared via ops.attention.core.window_too_far).
    ``local_flag`` (traced 0/1 scalar from attn_layer_pattern, or None)
    switches the window off for global layers inside the layer scan — the
    scan stays uniform while layers alternate (gpt_neo)."""
    from deepspeed_tpu.ops.attention.core import window_too_far

    far = window_too_far(q_glob[:, None], k_pos[None, :], c.sliding_window, local_flag)
    return jnp.where(far, jnp.float32(-1e30), jnp.float32(0.0))


def _rope_pairs(c: TransformerConfig, x, positions, seq_len=None):
    """Rotary on the ``qk_rope_dim`` dims of a latent-attention layer: x [t,
    heads, rot] at ``positions`` [t]. With ``rope_interleave`` dims (2i, 2i + 1)
    turn together (DeepseekV3's apply_rotary_pos_emb_interleave): they are laid
    out [evens | odds] first and then rotated in halves, the layout the result
    keeps: queries and keys get the same, and a dot product does not see it."""
    if c.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return _rope(x.transpose(1, 0, 2)[None], positions[None], c, seq_len)[0].transpose(1, 0, 2)


def latent_q(c: TransformerConfig, lp, a):
    """The query projection of one latent-attention layer on normed activations
    ``a`` [t, h], AS WRITTEN: [t, n_heads x head_dim], a head's ``[nope | rope]``
    dims on adjacent lanes, the rope dims not yet rotated (``latent_qkv`` splits
    and rotates them; the serving step's expanded chunk kernel reads the nope
    dims where they lie)."""
    from deepspeed_tpu.ops.normalization.fused_norm import rms_norm_reference

    if "wq" in lp:  # q_lora_rank 0: one projection
        q = as_written(_proj(c, a, lp["wq"]))
    else:
        eps = c.norm_eps if c.latent_norm_eps is None else c.latent_norm_eps
        cq = rms_norm_reference(_proj(c, a, lp["wq_a"]), lp["q_a_norm"], eps)
        q = as_written(_proj(c, cq, lp["wq_b"]))
    if c.latent_q_scale != 1.0:  # longcat_flash: behind wq_b, nope and rope dims alike
        q = (q.astype(jnp.float32) * c.latent_q_scale).astype(q.dtype)
    return q


def latent_qkv(c: TransformerConfig, lp, a, positions, seq_len=None, q=None):
    """The projections of one latent-attention layer on normed activations
    ``a`` [t, h] at ``positions`` [t]: (q_nope [t, nh, qk_nope_dim], q_rope
    [t, nh, qk_rope_dim] rotated, ckv [t, latent_dim]: the normed latent and
    the rotated key dims every head shares, which is what a cache holds of a
    token; under ``position="none"`` neither is rotated). ``q``: ``latent_q``'s
    result where the caller holds it already. Shared by the model's forward
    (expanded form) and the serving steps."""
    from deepspeed_tpu.ops.normalization.fused_norm import rms_norm_reference

    t = a.shape[0]
    nh, rank, dn = c.n_heads, c.kv_lora_rank, c.qk_nope_dim
    eps = c.norm_eps if c.latent_norm_eps is None else c.latent_norm_eps
    q = (latent_q(c, lp, a) if q is None else q).reshape(t, nh, c.head_dim)
    kv = _proj(c, a, lp["wkv_a"])
    latent = rms_norm_reference(kv[:, :rank], lp["kv_a_norm"], eps)
    if c.latent_kv_scale != 1.0:  # longcat_flash: the normed latent; the rotary key dims are not
        latent = (latent.astype(jnp.float32) * c.latent_kv_scale).astype(latent.dtype)
    if c.position == "rope":
        q_rope = _rope_pairs(c, q[..., dn:], positions, seq_len)
        k_rope = _rope_pairs(c, kv[:, None, rank:], positions, seq_len)[:, 0]
    else:  # "none" (kimi_linear's mla_use_nope): nothing turns
        q_rope, k_rope = q[..., dn:], kv[:, rank:]
    return q[..., :dn], q_rope, jnp.concatenate([latent, k_rope.astype(latent.dtype)], axis=-1)


def latent_up(c: TransformerConfig, lp):
    """``wkv_b`` split by head: (W_UK [rank, nh, qk_nope_dim], W_UV [rank, nh,
    v_head_dim]): a head's keys and values of the latent (expanded form), or
    moved to the query and the output side (absorbed form)."""
    from deepspeed_tpu.ops.stack_matmul import Stacked

    w = lp["wkv_b"]
    if isinstance(w, Stacked):  # the serving step keeps the stack for its chunk kernel
        w = w.stack[w.index]
    w = w.reshape(c.kv_lora_rank, c.n_heads, c.qk_nope_dim + c.v_head_dim)
    return w[..., : c.qk_nope_dim], w[..., c.qk_nope_dim:]


def _latent_attention_block(c: TransformerConfig, lp, x, positions, segment_ids):
    """Latent attention in the EXPANDED form (what training and a full
    forward run): every head's keys and values made of the latent. x: [b, s, h]."""
    b, s, h = x.shape
    nh, rank, d, dv = c.n_heads, c.kv_lora_rank, c.head_dim, c.v_head_dim
    if positions.ndim != 1:
        raise NotImplementedError("latent attention takes one [s] position vector a batch")
    w_uk, w_uv = latent_up(c, lp)

    def one(a):
        q_nope, q_rope, ckv = latent_qkv(c, lp, a, positions, s)
        k_nope = jnp.einsum("tc,chd->thd", ckv[:, :rank], w_uk)
        v = jnp.einsum("tc,chd->thd", ckv[:, :rank], w_uv)
        k_rope = jnp.broadcast_to(ckv[:, None, rank:], (s, nh, c.qk_rope_dim))
        return (jnp.concatenate([q_nope, q_rope], -1), jnp.concatenate([k_nope, k_rope], -1),
                # values ride the kernels at the keys' width; the pad is cut below
                jnp.pad(v, ((0, 0), (0, 0), (0, d - dv))))

    q, k, v = (t.transpose(0, 2, 1, 3) for t in jax.vmap(one)(x))
    out = attention_op(q, k, v, causal=True, segment_ids=segment_ids, scale=c.attn_scale,
                       impl=c.attention_impl)
    out = out[..., :dv].transpose(0, 2, 1, 3).reshape(b, s, nh * dv)
    return _proj(c, out, lp["wo"]), None


def kind_qkv(c: TransformerConfig, lp, a, positions, kind: str, seq_len=None):
    """The projections of one layer of a stack whose attention is stacked by
    kind (``attn_by_kind``) on normed activations ``a`` [t, h] at ``positions``
    [t]: (q [t, nh, d], k [t, nkv, d], v [t, nkv, dv]), the KV heads the
    layer's own (its ``wk`` says how many), rotary at its kind's base, the
    values under ``attn_value_scale``. Shared by the model's forward and the
    serving steps."""
    t = a.shape[0]
    nh, d, dv = c.n_heads, c.head_dim, c.value_dim
    q, k, v = _proj(c, a, lp["wq"]), _proj(c, a, lp["wk"]), _proj(c, a, lp["wv"])
    nkv = k.shape[-1] // d
    q, k, v = q.reshape(t, nh, d), k.reshape(t, nkv, d), v.reshape(t, nkv, dv)
    if c.position == "rope":
        theta = c.rope_theta_of(kind)
        q = _rope(q.transpose(1, 0, 2)[None], positions[None], c, seq_len, theta)[0].transpose(1, 0, 2)
        k = _rope(k.transpose(1, 0, 2)[None], positions[None], c, seq_len, theta)[0].transpose(1, 0, 2)
    if c.attn_value_scale != 1.0:
        v = (v.astype(jnp.float32) * c.attn_value_scale).astype(v.dtype)
    return q, k, v


def _kind_attention_block(c: TransformerConfig, lp, x, positions, segment_ids, window: bool):
    """Self-attention of one layer of an ``attn_by_kind`` stack, as plain dense
    algebra (what ``forward`` runs; the flash kernels take neither a value
    width of its own nor a sink): causal, within ``sliding_window`` on a
    window layer, whose softmax also has the heads' learned sinks in its
    denominator. x: [b, s, h]."""
    b, s, h = x.shape
    nh, d, dv = c.n_heads, c.head_dim, c.value_dim
    if positions.ndim != 1:
        raise NotImplementedError("attention stacked by kind takes one [s] position vector a batch")
    kind = "window" if window else "full"
    q, k, v = jax.vmap(lambda a: kind_qkv(c, lp, a, positions, kind, s))(x)
    nkv = k.shape[2]
    qg = q.reshape(b, s, nkv, nh // nkv, d).astype(jnp.float32)
    scores = jnp.einsum("bingd,bjnd->bngij", qg, k.astype(jnp.float32)) * (
        c.attn_scale if c.attn_scale is not None else d ** -0.5)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None]
    mask = j <= i
    if window:
        mask = mask & (i - j < c.sliding_window)
    mask = mask[None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, :, None] == segment_ids[:, None, :])
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    if window and c.attn_sink_window:
        sink = jnp.broadcast_to(
            lp["sink"].astype(jnp.float32).reshape(1, nkv, nh // nkv, 1, 1), scores.shape[:-1] + (1,))
        w = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    else:
        w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngij,bjnd->bingd", w, v.astype(jnp.float32)).astype(x.dtype)
    return _proj(c, out.reshape(b, s, nh * dv), lp["wo"]), None


def _attention_block(c: TransformerConfig, lp, x, positions, segment_ids, kv_cache=None,
                     local_flag=None):
    """Self-attention for one layer. x: [b, s, h]."""
    if c.attn_by_kind:
        if kv_cache is not None:
            raise NotImplementedError(
                "attention stacked by kind has no v1 decode step: serve it through InferenceEngineV2")
        return _kind_attention_block(c, lp, x, positions, segment_ids, bool(local_flag))
    if c.latent:
        if kv_cache is not None:
            raise NotImplementedError(
                "latent attention has no v1 decode step: serve it through InferenceEngineV2")
        return _latent_attention_block(c, lp, x, positions, segment_ids)
    b, s, h = x.shape
    nh, nkv, d = c.n_heads, c.kv_heads, c.head_dim
    q = _proj(c, x, lp["wq"])
    k = _proj(c, x, lp["wk"])
    v = _proj(c, x, lp["wv"])
    if c.attn_qkv_bias:
        q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
    if qk_norm_full(c):
        q = qk_norm_apply(c, q, lp["q_norm"], head_axis=-1)
        k = qk_norm_apply(c, k, lp["k_norm"], head_axis=-1)
    # q and k a head at a time WHERE THE PROJECTIONS WROTE THEM (heads_view:
    # [b, s / 8, heads, 8, d], the same bytes as [b, s, heads * d]): the norm
    # and rope are a token's and a head's own, and the flash kernels index a
    # head's lanes out of [b, s, heads * d] in place (attention_op, rank 3).
    # A path that wants [b, heads, s, d] transposes where it is chosen, below.
    # Pinned as the products wrote them: unpinned, the chip's compiler folds the
    # norm's and rope's gradients into the operands of BOTH backward products
    # of wq and wk and runs them twice at the matrix unit's pace (17,570-17,617
    # tokens/s against 17,864-17,869 pinned: my chip runs, PR 59).
    q, k = as_written((q, k))
    q, k = heads_view(q, nh), heads_view(k, nkv)
    if c.qk_norm and not qk_norm_full(c):
        # qwen3 rmsnorm / phi affine layernorm / stablelm-2 per-head, pre-rope
        q = qk_norm_apply(c, q, lp["q_norm"], head_axis=2, b=lp.get("q_norm_b"))
        k = qk_norm_apply(c, k, lp["k_norm"], head_axis=2, b=lp.get("k_norm_b"))
    if c.position == "rope":
        # seq len: the LIVE sequence length (HF's max(position_ids)+1) — in
        # decode that is cache fill + this block, traced; else the static s
        seq_len = kv_cache[2] + s if kv_cache is not None else s
        at = tokens_view(positions if positions.ndim > 1 else positions[None], s)
        q_r = _rope(q, at, c, seq_len, head_axis=2)
        k_r = _rope(k, at, c, seq_len, head_axis=2)
        if c.rope_window_only:  # a global layer (flag 0) attends with no position term
            q_r, k_r = jnp.where(local_flag > 0, q_r, q), jnp.where(local_flag > 0, k_r, k)
        q, k = q_r, k_r

    def heads_major(*xs):  # the paths that take [b, heads, s, d]
        return tuple(head_major(x, d) for x in xs)

    q, k = heads_flat(q), heads_flat(k)
    new_cache = None
    if kv_cache is not None:
        # decode: append to cache along seq
        q, k, v = heads_major(q, k, v)
        ck, cv, clen = kv_cache  # [b, nkv, S, d], [b, nkv, S, d], scalar
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, clen, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, clen, axis=2)
        k, v = ck, cv
        new_cache = (ck, cv, clen + s)
        S = ck.shape[2]
        # causal within the new block AND bounded by the filled cache: query i
        # (global position clen+i) sees keys at positions <= clen+i only.
        q_glob = clen + jnp.arange(s)  # [s]
        kpos = jnp.arange(S)  # [S]
        mask_bias = jnp.where(kpos[None, :] <= q_glob[:, None], 0.0, -1e30).astype(jnp.float32)
        if c.sliding_window > 0:
            mask_bias = mask_bias + _window_bias(c, q_glob, kpos, local_flag)
        bias = mask_bias[None, None]
        if c.position == "alibi":
            bias = bias + _alibi_bias(c, kpos)
        out = token_major(attention_op(q, k, v, causal=False, bias=bias, scale=c.attn_scale))
    else:
        topo = get_topology()
        impl = c.attention_impl
        if impl == "auto" and topo.context_parallel_size > 1:
            impl = "flash_ring"
        if impl == "flash_ring":
            # context parallelism: the ring shards the sequence dim itself
            # (O(s/N) per-device activations); dispatch through the
            # ops.attention seam so sharding constraints are pinned there
            if topo.sequence_parallel_size > 1:
                raise NotImplementedError(
                    "ring context parallelism combined with sequence "
                    "parallelism (Ulysses within a context shard) is not "
                    "wired in the model attention block yet"
                )
            if not c.attn_causal:
                raise NotImplementedError(
                    "ring context parallelism is causal-only (the ring "
                    "schedule streams the causal triangle)"
                )
            if c.sliding_window:
                raise NotImplementedError(
                    "sliding_window under ring context parallelism is not "
                    "supported (band masks are global-position)"
                )
            out = token_major(attention_op(
                *heads_major(q, k, v), causal=True, segment_ids=segment_ids,
                scale=c.attn_scale, impl="flash_ring",
                alibi_slopes=(jnp.asarray(alibi_slopes(nh))
                              if c.position == "alibi" else None),
            ))
        elif topo.sequence_parallel_size > 1:
            if c.position == "alibi":
                raise NotImplementedError(
                    "alibi attention under sequence parallelism is not supported "
                    "(the ring/ulysses kernels take no bias)"
                )
            if not c.attn_causal:
                raise NotImplementedError(
                    "bidirectional attention under sequence parallelism is "
                    "not supported (the ring/ulysses paths are causal)"
                )
            if c.seq_impl == "ring":
                from deepspeed_tpu.parallel.sequence import ring_attention

                # window masks over GLOBAL positions inside the ring loop
                out = token_major(ring_attention(
                    *heads_major(q, k, v), causal=True, segment_ids=segment_ids,
                    scale=c.attn_scale, window=c.sliding_window,
                    window_flag=local_flag,
                ))
            else:
                from deepspeed_tpu.parallel.sequence import ulysses_attention

                out = token_major(ulysses_attention(
                    *heads_major(q, k, v), causal=True, segment_ids=segment_ids,
                    scale=c.attn_scale, window=c.sliding_window,
                    window_flag=local_flag,
                ))
        elif c.position == "alibi":
            # rank-1 form rides the flash kernel (slope * key_position added
            # in-kernel) — the dense [s, s] bias never materializes
            out = attention_op(
                q, k, v, causal=True, segment_ids=segment_ids,
                alibi_slopes=jnp.asarray(alibi_slopes(nh)),
                alibi_positions=positions, impl=impl, head_dim=d,
            )
        else:
            # sliding windows ride the flash kernel (in-kernel band mask;
            # static windows — no attn_layer_pattern — additionally prune
            # out-of-band kv blocks, O(s·window) compute); window distance is
            # the token index, packing composes via segment_ids.
            # splash: the mask compiles into a compacted block schedule at
            # trace time (lru-cached Python constant); masked blocks never
            # enter the kernel grid. attn_sparsity promotes "auto" too.
            schedule = None
            if c.attn_sparsity is not None:
                schedule = _sparsity_schedule(
                    c.attn_sparsity, nh, s, c.splash_block, c.attn_causal)
            elif impl == "splash" and c.splash_block:
                from deepspeed_tpu.ops.attention.core import _derived_splash_schedule

                schedule = _derived_splash_schedule(
                    s, s, c.attn_causal, c.sliding_window, c.splash_block)
            out = attention_op(
                q, k, v, causal=c.attn_causal, segment_ids=segment_ids,
                scale=c.attn_scale, window=c.sliding_window,
                window_flag=local_flag, impl=impl, schedule=schedule,
                head_dim=d,
            )
    if c.attn_out_gate:
        out = attn_gate(out, _proj(c, x, lp["wq_gate"]))
    out = _proj(c, out, lp["wo"])
    if c.attn_out_bias:
        out = out + lp["wo_b"]
    return out, new_cache


def attn_gate(out, gate):
    """The heads' output under its gate: ``out * sigmoid(gate)`` (qwen3-next)."""
    return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)


def gdn_project(c: TransformerConfig, lp, a):
    """A DeltaNet layer's projections of the normed input ``a [..., h]``: (the
    conv's input ``[..., C]``: q | k at the key heads | v, the output gate z
    ``[..., nv, dv]``, g and beta ``[..., nv]`` float32)."""
    from deepspeed_tpu.ops.linear_attention import gdn_gates

    nv = c.gdn_value_heads
    z = as_written(_proj(c, a, lp["gdn_z"]))
    ba = a @ lp["gdn_ba"]
    g, beta = gdn_gates(ba[..., :nv], ba[..., nv:], lp["gdn_a_log"], lp["gdn_dt_bias"])
    return _proj(c, a, lp["gdn_qkv"]), z.reshape(z.shape[:-1] + (nv, c.gdn_value_dim)), g, beta


def gdn_heads(c: TransformerConfig, qkv):
    """The conv's output ``[..., C]`` as the rule takes it: q, k
    ``[..., nk, dk]`` L2-normalised (q scaled), v ``[..., nv, dv]``, float32."""
    from deepspeed_tpu.ops.linear_attention.gated_delta import qk_heads

    nk, dk, nv, dv = c.gdn_key_heads, c.gdn_key_dim, c.gdn_value_heads, c.gdn_value_dim
    lead = qkv.shape[:-1]
    q, k, v = jnp.split(qkv.astype(jnp.float32), [nk * dk, 2 * nk * dk], axis=-1)
    q, k = qk_heads(q.reshape(lead + (nk, dk)), k.reshape(lead + (nk, dk)))
    return q, k, v.reshape(lead + (nv, dv))


def gdn_output(c: TransformerConfig, lp, o, z, dtype):
    """The rule's output ``o [..., nv, dv]`` through the gated norm and the
    output projection: ``[..., h]``."""
    from deepspeed_tpu.ops.linear_attention import gated_rms_norm

    y = gated_rms_norm(o, z, lp["gdn_norm"], c.norm_eps).astype(dtype)
    return _proj(c, y.reshape(y.shape[:-2] + (-1,)), lp["gdn_out"])


def mamba_select(c: TransformerConfig, lp, u):
    """What a Mamba layer reads off its conv's output ``u [..., d]`` (float32):
    (the step size delta ``[..., d]`` float32, B and C ``[..., N]``), each of dt,
    B and C through its RMSNorm (Jamba's addition to Mamba-1)."""
    R, N = c.mamba_dt_rank, c.mamba_d_state
    dtype = DTYPES[c.dtype]
    x = _proj(c, u.astype(dtype), lp["mamba_x"])
    dt, B, C = (_norm(a, lp[k], None, "rmsnorm", c.norm_eps) for a, k in (
        (x[..., :R], "mamba_dt_norm"), (x[..., R : R + N], "mamba_b_norm"),
        (x[..., R + N :], "mamba_c_norm")))
    delta = jax.nn.softplus(
        (dt @ lp["mamba_dt"]).astype(jnp.float32) + lp["mamba_dt_b"].astype(jnp.float32))
    return delta, B, C


class RecurrentKind(NamedTuple):
    """A recurrent layer kind: what a sequence keeps for one such layer (a
    float32 state and the last ``kernel - 1`` inputs of a short causal conv
    over ``channels``, in the compute dtype) and its two rules. ``models.forward``
    and the served step (``engine_v2._recurrent_layer``, which owns the slots)
    read a kind through this description alone.

      project(c, lp, a [t, h]) -> (the conv's input [t, channels], extras: a
          tuple of arrays [t, ...] the rules and ``output`` take back)
      decode(c, lp, y [R, channels], extras, live [R], pool, slots, impl) ->
          (o [R, ...], pool): one token a row on the rows' states IN the pool; a
          row that is not live leaves its state as it was
      chunk(c, lp, y [r, t, channels], extras, live [r, t], state [r, ...], impl) ->
          (o [r, t, ...], the states after the rows' live tokens)
      output(c, lp, o [t, ...], extras, dtype) -> [t, h]
    """
    words: str                      # the refusals' words for what a sequence holds
    state_shape: Callable           # c -> a layer's state a sequence
    channels: Callable              # c -> the conv's channels
    kernel: Callable                # c -> the conv's taps
    conv_keys: Tuple[str, Optional[str]]  # the conv's weight and bias in ``lp``
    project: Callable
    decode: Callable
    chunk: Callable
    output: Callable


def _gdn_project(c, lp, a):
    qkv, z, g, beta = gdn_project(c, lp, a)
    return qkv, (z, g, beta)


def _gdn_decode(c, lp, y, extras, live, pool, slots, impl):
    from deepspeed_tpu.ops.linear_attention import gdn_decode

    _, g, beta = extras
    q, k, v = gdn_heads(c, y)
    return gdn_decode(q, k, v, jnp.where(live[:, None], g, 0.0),
                      jnp.where(live[:, None], beta, 0.0), pool, slots, impl=impl)


def _gdn_chunk(c, lp, y, extras, live, state, impl=None):
    from deepspeed_tpu.ops.linear_attention import gdn_chunked

    _, g, beta = extras
    q, k, v = gdn_heads(c, y)
    return gdn_chunked(q, k, v, jnp.where(live[..., None], g, 0.0),
                       jnp.where(live[..., None], beta, 0.0), state, impl=impl)


def _mamba_project(c, lp, a):
    uz = _proj(c, a, lp["mamba_in"])
    return uz[..., : c.mamba_d_inner], (uz[..., c.mamba_d_inner :],)


def _mamba_rule(c, lp, u, live):
    """(delta, zero where a token is not live; B; C; A = -exp(A_log); D)."""
    delta, B, C = mamba_select(c, lp, u)
    return (jnp.where(live[..., None], delta, 0.0), B, C,
            -jnp.exp(lp["mamba_a_log"].astype(jnp.float32)), lp["mamba_d"])


def _mamba_decode(c, lp, y, extras, live, pool, slots, impl):
    from deepspeed_tpu.ops.state_space import mamba_decode

    delta, B, C, A, D = _mamba_rule(c, lp, y, live)
    return mamba_decode(y, delta, B, C, extras[0], A, D, pool, slots, impl=impl)


def _mamba_chunk(c, lp, y, extras, live, state, impl=None):
    from deepspeed_tpu.ops.state_space import mamba_scan

    delta, B, C, A, D = _mamba_rule(c, lp, y, live)
    return mamba_scan(y, delta, B, C, extras[0], A, D, state, impl=impl)


def _kda_project(c, lp, a):
    """A KDA layer's projections of the normed input ``a [..., h]``: (the conv's
    input ``[..., 3 H d]``: q | k | v, (the output gate's input ``[..., H, d]``, g
    ``[..., H, d]`` float32: a decay a key channel, beta ``[..., H]`` float32))."""
    f32 = jnp.float32
    H, d = c.kda_heads, c.kda_head_dim
    heads = lambda x: x.reshape(x.shape[:-1] + (H, d))  # noqa: E731
    f = heads(((a @ lp["kda_f_a"]) @ lp["kda_f_b"]).astype(f32) + lp["kda_dt_bias"].astype(f32))
    g = -jnp.exp(lp["kda_a_log"].astype(f32))[:, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid((a @ lp["kda_b"]).astype(f32))
    z = heads(as_written((a @ lp["kda_g_a"]) @ lp["kda_g_b"]))
    return _proj(c, a, lp["kda_qkv"]), (z, g, beta)


def _kda_heads(c, y):
    """The conv's output ``[..., 3 H d]`` as the rule takes it: q, k ``[..., H,
    d]`` L2-normalised (q scaled), v ``[..., H, d]``, float32."""
    from deepspeed_tpu.ops.linear_attention.gated_delta import qk_heads

    q, k, v = (x.reshape(x.shape[:-1] + (c.kda_heads, c.kda_head_dim))
               for x in jnp.split(y.astype(jnp.float32), 3, axis=-1))
    return qk_heads(q, k) + (v,)


def _kda_decode(c, lp, y, extras, live, pool, slots, impl):
    from deepspeed_tpu.ops.linear_attention import kda_decode

    _, g, beta = extras
    return kda_decode(*_kda_heads(c, y), jnp.where(live[:, None, None], g, 0.0),
                      jnp.where(live[:, None], beta, 0.0), pool, slots, impl=impl)


def _kda_chunk(c, lp, y, extras, live, state, impl=None):
    from deepspeed_tpu.ops.linear_attention import kda_chunked

    _, g, beta = extras
    return kda_chunked(*_kda_heads(c, y), jnp.where(live[..., None, None], g, 0.0),
                       jnp.where(live[..., None], beta, 0.0), state, impl=impl)


def _kda_output(c, lp, o, extras, dtype):
    """The rule's output through the head's RMSNorm under the SIGMOID gate, and
    the output projection."""
    from deepspeed_tpu.ops.linear_attention import gated_rms_norm

    y = gated_rms_norm(o, extras[0], lp["kda_norm"], c.norm_eps, jax.nn.sigmoid).astype(dtype)
    return _proj(c, y.reshape(y.shape[:-2] + (-1,)), lp["kda_out"])


def _mamba_state_shape(c):
    from deepspeed_tpu.ops.state_space import state_shape

    return state_shape(c.mamba_d_inner, c.mamba_d_state)


RECURRENT: Dict[str, RecurrentKind] = {
    "gdn": RecurrentKind(
        words="Gated DeltaNet layers keep a recurrent state",
        state_shape=lambda c: (c.gdn_value_heads, c.gdn_key_dim, c.gdn_value_dim),
        channels=lambda c: c.gdn_conv_dim, kernel=lambda c: c.gdn_conv_kernel,
        conv_keys=("gdn_conv", None),
        project=_gdn_project, decode=_gdn_decode, chunk=_gdn_chunk,
        output=lambda c, lp, o, extras, dtype: gdn_output(c, lp, o, extras[0], dtype)),
    "mamba": RecurrentKind(
        words="Mamba layers keep a selective state-space state",
        state_shape=_mamba_state_shape,
        channels=lambda c: c.mamba_d_inner, kernel=lambda c: c.mamba_conv_kernel,
        conv_keys=("mamba_conv", "mamba_conv_b"),
        project=_mamba_project, decode=_mamba_decode, chunk=_mamba_chunk,
        # (the scan gates its output by silu(z) itself)
        output=lambda c, lp, o, extras, dtype: _proj(c, o.astype(dtype), lp["mamba_out"])),
    "kda": RecurrentKind(
        words="Kimi Delta Attention layers keep a recurrent state",
        state_shape=lambda c: (c.kda_heads, c.kda_head_dim, c.kda_head_dim),
        channels=lambda c: 3 * c.kda_heads * c.kda_head_dim, kernel=lambda c: c.kda_conv_kernel,
        conv_keys=("kda_conv", None),
        project=_kda_project, decode=_kda_decode, chunk=_kda_chunk, output=_kda_output),
}


def recurrent_conv(kind: RecurrentKind, lp, x, state, n=None):
    """The kind's short causal conv (``causal_conv``) with the layer's weight
    and, where the kind has one, bias."""
    from deepspeed_tpu.ops.linear_attention import causal_conv

    w, b = kind.conv_keys
    return causal_conv(x, lp[w], state, n=n, bias=lp[b] if b else None)


def _recurrent_block(c: TransformerConfig, lp, x):
    """A recurrent layer (``RECURRENT``) with no cache: conv and state start
    from zero. x: [b, s, h] (normed). The two delta rules run their ``"jnp"``
    bodies here whatever the platform: training differentiates this path, XLA's
    autodiff goes through the chunked form and a ``pallas_call`` has no gradient
    (the served step names no ``impl`` and takes the kernels on a TPU)."""
    kind = RECURRENT[c.recurrent_kind]
    b, s = x.shape[:2]
    y, extras = kind.project(c, lp, x)
    y, _ = recurrent_conv(kind, lp, y, jnp.zeros((b, kind.kernel(c) - 1, kind.channels(c)), x.dtype))
    o, _ = kind.chunk(c, lp, y, extras, jnp.ones((b, s), bool),
                      jnp.zeros((b,) + kind.state_shape(c), jnp.float32),
                      "jnp" if c.recurrent_kind in ("gdn", "kda") else None)
    return kind.output(c, lp, o, extras, x.dtype)


def _mlp_block(c: TransformerConfig, lp, x):
    if "router" in lp:  # an expert layer: its own keys say so (moe_dense_lead)
        from deepspeed_tpu.parallel.moe import moe_mlp

        return moe_mlp(c, lp, x)[:2]
    up = _proj(c, x, lp["w_up"])
    if c.mlp_bias:
        up = up + lp["w_up_b"]
    if c.activation in ("swiglu", "geglu"):
        gate = _proj(c, x, lp["w_gate"])
        if c.mlp_bias:
            gate = gate + lp["w_gate_b"]
        act = (jax.nn.gelu(gate) if c.activation == "geglu" else jax.nn.silu(gate)) * up
    elif c.activation == "relu":
        act = jax.nn.relu(up)
    elif c.activation == "quick_gelu":
        act = up * jax.nn.sigmoid(1.702 * up)
    else:
        act = jax.nn.gelu(up, approximate=c.activation != "gelu_exact")
    out = _proj(c, act, lp["w_down"])
    if c.mlp_bias:
        out = out + lp["w_down_b"]
    return out, jnp.float32(0.0)


def _dequant_tree(lp, dtype):
    """Transparent weight-only quantized inference: QuantizedWeight leaves
    (inference/quantization) widen HERE — inside the layer scan body — so
    the transient bf16 copy is one layer, never the model."""
    try:
        from deepspeed_tpu.inference.quantization.quantize import (
            is_quantized_leaf,
            maybe_dequantize,
        )
    except ImportError:  # quantization package optional at import time
        return lp
    if not any(
        is_quantized_leaf(l)
        for l in jax.tree_util.tree_leaves(lp, is_leaf=is_quantized_leaf)
    ):
        return lp
    return jax.tree.map(
        lambda n: maybe_dequantize(n, dtype), lp, is_leaf=is_quantized_leaf
    )


def _shortcut_layer(c: TransformerConfig, lp, x, positions, segment_ids):
    """One ``moe_shortcut`` layer (longcat_flash) as published: two sub-blocks
    of latent attention and dense MLP on the stream, and the expert block, which
    reads the FIRST sub-block's normed MLP input and joins the stream behind
    the SECOND sub-block's MLP. ``lp``: the expert block's parameters and
    under "sub" the sub-blocks' stacked [2, ...]."""
    shortcut = aux_loss = None
    for i in range(2):
        sp = jax.tree.map(lambda a: a[i], lp["sub"])
        with jax.named_scope(f"sub_block_{i}"):
            a = _norm(x, sp["attn_norm"], None, c.norm, c.norm_eps)
            x = _act_constraint(x + _attention_block(c, sp, a, positions, segment_ids)[0])
            m = _norm(x, sp["mlp_norm"], None, c.norm, c.norm_eps)
            if i == 0:
                with jax.named_scope("shortcut_experts"):
                    shortcut, aux_loss = _mlp_block(c, lp, m)
            x = x + _mlp_block(c, sp, m)[0]
    return _act_constraint(x + shortcut), aux_loss


def _layer(c: TransformerConfig, lp, x, positions, segment_ids, local_flag=None):
    lp = _dequant_tree(lp, DTYPES[c.dtype])
    # Autocast: run the layer at the model's configured compute dtype even
    # when the engine hands in wider params (e.g. fp32 masters with no bf16
    # block in the DS config). Without this, f32 weights promote the residual
    # stream and the layer-scan carry dtype flips mid-scan.
    dt = DTYPES[c.dtype]
    lp = jax.tree.map(
        lambda w: w.astype(dt)
        if hasattr(w, "dtype") and jnp.issubdtype(w.dtype, jnp.floating) and w.dtype != dt
        else w,
        lp,
    )
    if "sub" in lp:  # a layer of two sub-blocks (moe_shortcut): its own keys say so
        return _shortcut_layer(c, lp, x, positions, segment_ids)
    if c.norm_scheme == "post":
        # BERT: norm AFTER each residual add; attention reads the raw stream
        attn_out, _ = _attention_block(c, lp, x, positions, segment_ids, local_flag=local_flag)
        x = _norm(x + attn_out, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        x = _act_constraint(x)
        mlp_out, aux_loss = _mlp_block(c, lp, x)
        x = _norm(x + mlp_out, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        return _act_constraint(x), aux_loss
    if c.norm_scheme == "out":
        # exaone4: each block reads the raw stream and its OUTPUT is normed
        attn_out, _ = _attention_block(c, lp, x, positions, segment_ids, local_flag=local_flag)
        x = _act_constraint(
            x + _norm(attn_out, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps))
        mlp_out, aux_loss = _mlp_block(c, lp, x)
        x = x + _norm(mlp_out, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        return _act_constraint(x), aux_loss
    a = _norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
    if c.hybrid and "wo" not in lp:  # a recurrent layer (layer_kinds): no attention's keys
        if segment_ids is not None:
            raise NotImplementedError("packed sequences through a recurrent layer: the "
                                      "state would have to reset at each boundary")
        attn_out = _recurrent_block(c, lp, a)
    else:
        attn_out, _ = _attention_block(c, lp, a, positions, segment_ids, local_flag=local_flag)
    if c.parallel_block:
        # falcon/phi: both branches from the pre-attention state, one residual
        m = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        mlp_out, aux_loss = _mlp_block(c, lp, m)
        x = x + attn_out + mlp_out
        return _act_constraint(x), aux_loss
    x = x + attn_out
    x = _act_constraint(x)
    m = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
    mlp_out, aux_loss = _mlp_block(c, lp, m)
    x = x + mlp_out
    x = _act_constraint(x)
    return x, aux_loss


def forward_hidden(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: TransformerConfig,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    token_type_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Body forward: tokens [b, s] → (final-norm'd hidden [b, s, h], aux_loss).

    Layers run under ``lax.scan`` over the stacked layer pytree; with
    ``config.remat`` each layer is rematerialized and keeps only what
    ``remat_policy(config.remat_policy)`` names (by default its products'
    outputs and the attention kernel's result), so the backward recomputes
    the elementwise work and no product or kernel.
    """
    c = config
    b, s = tokens.shape
    stream = _stream_active(c)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    embed = _maybe_stage(params["embed"]) if stream else params["embed"]
    x = _scale_embed(embed.astype(DTYPES[c.dtype])[tokens], c, DTYPES[c.dtype])
    if c.position == "learned":
        pe = _maybe_stage(params["pos_embed"]) if stream else params["pos_embed"]
        x = x + pe[positions][None] if positions.ndim == 1 else x + pe[positions]
    if c.type_vocab_size > 0:
        te = _maybe_stage(params["type_embed"]) if stream else params["type_embed"]
        te = te.astype(x.dtype)
        # default token type 0 (HF convention when token_type_ids is omitted)
        x = x + (te[0] if token_type_ids is None else te[token_type_ids])
    if c.embed_norm:
        x = _embed_norm(params, c, x, stream)
    x = _act_constraint(x)

    layer_fn = partial(_layer, c)
    if stream:
        # stage INSIDE the (remat'd) layer body: forward brings one layer's
        # weights to HBM per scan step, backward re-stages them on recompute
        inner_fn = layer_fn
        layer_fn = lambda lp, *a: inner_fn(_stage_tree(lp), *a)  # noqa: E731
    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c.remat_policy))

    if c.hybrid and c.moe_dense_lead:
        # kinds of layer AND lead layers whose MLP is of another shape: every
        # layer unrolled, its parameters out of its own sub-stacks (layer_stacks)
        auxs = []
        for li in range(c.n_layers):
            x, aux = layer_fn(take_layer(params["layers"], c, li, lambda a, i: a[i]),
                              x, positions, segment_ids)
            auxs.append(aux)
        aux_losses, xs = jnp.stack(auxs), None
    elif c.hybrid:
        # two kinds of layer: one scan step a PERIOD of the pattern, the
        # period's layers unrolled in it, each kind's stack split by period
        period, n = layer_period(c)
        ords = kind_ordinals(c)[: len(period)]
        pl_ = params["layers"]
        by_period = {
            kind: jax.tree.map(
                lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), pl_[kind])
            for kind in set(period)}
        common = jax.tree.map(
            lambda a: a.reshape((n, len(period)) + a.shape[1:]),
            {k: v for k, v in pl_.items() if not isinstance(v, dict)})

        def period_body(x, xs_p):
            common_p, kinds_p = xs_p
            aux = jnp.float32(0.0)
            for j, kind in enumerate(period):
                lp = {**jax.tree.map(lambda a: a[j], common_p),
                      **jax.tree.map(lambda a: a[ords[j]], kinds_p[kind])}
                x, a = layer_fn(lp, x, positions, segment_ids)
                aux = aux + a
            return x, aux

        x, aux_losses = jax.lax.scan(period_body, x, (common, by_period))
        xs = None
    elif c.attn_by_kind:
        # attention of two shapes: every layer unrolled, its parameters out of
        # its own sub-stacks (layer_stacks), its kind a static flag
        def kind_fn(flag):
            def fn(lp, x):
                return _layer(c, _stage_tree(lp) if stream else lp, x, positions, segment_ids, flag)
            return jax.checkpoint(fn, policy=remat_policy(c.remat_policy)) if c.remat else fn

        fns, auxs = {f: kind_fn(f) for f in (0, 1)}, []
        for li, flag in enumerate(c.attn_layer_pattern):
            x, aux = fns[int(flag)](take_layer(params["layers"], c, li, lambda a, i: a[i]), x)
            auxs.append(aux)
        aux_losses, xs = jnp.stack(auxs), None
    elif c.moe_dense_lead:
        # the lead layers first, unrolled (their MLP is of another shape), then
        # the scan over what every layer has and the expert block behind them
        lead, pl_ = c.moe_dense_lead, params["layers"]
        common = {k: v for k, v in pl_.items() if not isinstance(v, dict)}
        flags = jnp.asarray(c.attn_layer_pattern or (0,) * c.n_layers, jnp.int32)
        for i in range(lead):
            lp = {**jax.tree.map(lambda a: a[i], common),
                  **jax.tree.map(lambda a: a[i], pl_["lead"])}
            x, _ = layer_fn(lp, x, positions, segment_ids, flags[i])  # a dense MLP: no aux
        xs = ({**jax.tree.map(lambda a: a[lead:], common), **pl_["sparse"]}, flags[lead:])

        def call_layer(xs_i, x):
            lp, flag = xs_i
            return layer_fn(lp, x, positions, segment_ids, flag)
    elif c.attn_layer_pattern is not None:
        flags = jnp.asarray(c.attn_layer_pattern, jnp.int32)
        xs = (params["layers"], flags)

        def call_layer(xs_i, x):
            lp, flag = xs_i
            return layer_fn(lp, x, positions, segment_ids, flag)
    else:
        xs = params["layers"]
        if c.moe_shortcut:  # a layer's two sub-blocks: [2 L, ...] as [L, 2, ...]
            xs = dict(xs, sub=jax.tree.map(
                lambda a: a.reshape((c.n_layers, 2) + a.shape[1:]), xs["sub"]))

        def call_layer(xs_i, x):
            return layer_fn(xs_i, x, positions, segment_ids)

    n_layer = jax.tree_util.tree_leaves(xs)[0].shape[0] if xs is not None else 0
    chunk = _OVERLAP_SCAN_CHUNK
    if xs is None:
        pass  # the hybrid stack ran above
    elif chunk > 1 and n_layer % chunk == 0:
        # bucketed prefetch: scan L/chunk chunks, the inner `chunk` layers
        # unrolled so layer b+1's weight gather/stage (which stays INSIDE
        # the remat'd layer body — hoisting it out would pin every gathered
        # layer as a saved residual) sits in the same scan body as layer
        # b's compute, where the scheduler can overlap them
        xs_c = jax.tree.map(
            lambda a: a.reshape((n_layer // chunk, chunk) + a.shape[1:]), xs
        )

        def scan_body(carry, xs_b):
            x = carry
            auxs = []
            for b_i in range(chunk):
                x, aux = call_layer(jax.tree.map(lambda a: a[b_i], xs_b), x)
                auxs.append(aux)
            return x, jnp.stack(auxs)

        x, aux_losses = jax.lax.scan(scan_body, x, xs_c)
    else:

        def scan_body(carry, xs_i):
            return call_layer(xs_i, carry)

        x, aux_losses = jax.lax.scan(scan_body, x, xs)
    if c.final_norm:
        fn_w = _maybe_stage(params["final_norm"]) if stream else params["final_norm"]
        fn_b = params.get("final_norm_b")
        if stream and fn_b is not None:
            fn_b = _maybe_stage(fn_b)
        x = _norm(x, fn_w, fn_b, c.norm, c.norm_eps)
    return x, jnp.sum(aux_losses)


def _embed_norm(params, c: TransformerConfig, x, stream: bool):
    """bloom word_embeddings_layernorm applied to the embedding output."""
    w = _maybe_stage(params["embed_norm"]) if stream else params["embed_norm"]
    b = _maybe_stage(params["embed_norm_b"]) if stream else params["embed_norm_b"]
    return _norm(x, w, b, "layernorm", c.norm_eps)


def _lm_head_matrix(params, config: TransformerConfig, dtype):
    stream = _stream_active(config)
    if config.tie_embeddings:
        w = params["embed"]
        return (_maybe_stage(w) if stream else w).astype(dtype).T
    w = _dequant_tree(params["lm_head"], dtype)
    return _maybe_stage(w) if stream else w


def _apply_lm_head(params, x, config: TransformerConfig):
    logits = x @ _lm_head_matrix(params, config, x.dtype)
    if config.lm_head_bias and not config.tie_embeddings:
        logits = logits + params["lm_head_b"].astype(logits.dtype)
    return logits


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: TransformerConfig,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    token_type_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full forward: tokens [b, s] int32 → (logits [b, s, vocab], aux_loss)."""
    x, aux = forward_hidden(params, tokens, config, positions, segment_ids, token_type_ids)
    if config.mlm_head:
        # BertForMaskedLM cls.predictions: transform (dense + act + LN), then
        # the tied decoder with its standalone vocab bias
        c = config
        t = x @ params["mlm_dense"].astype(x.dtype) + params["mlm_dense_b"].astype(x.dtype)
        # the transform uses the config's hidden activation (HF ACT2FN)
        if c.activation == "relu":
            t = jax.nn.relu(t)
        else:
            t = jax.nn.gelu(t, approximate=c.activation != "gelu_exact")
        t = _norm(t, params["mlm_norm"], params["mlm_norm_b"], "layernorm", c.norm_eps)
        return _apply_lm_head(params, t, c) + params["mlm_bias"].astype(x.dtype), aux
    return _apply_lm_head(params, x, config), aux


def decode_step(params, tokens, config, kv_caches, positions):
    """Single decode step with KV caches (inference path).

    tokens: [b, t] new tokens; kv_caches: per-layer list of (k, v, len).
    Returns (logits [b, t, vocab], new_caches). Runs layers as a Python loop
    over unstacked weights (decode graphs are small; scan would force cache
    stacking anyway, which we do — caches are stacked [L, ...]).
    """
    c = config
    if not c.attn_causal:
        raise ValueError(
            "decode_step: bidirectional encoder models (attn_causal=False) "
            "do not autoregressively decode — call forward() instead"
        )
    if c.norm_scheme == "out" or c.moe_dense_lead or c.moe_shortcut:
        raise NotImplementedError(
            "decode_step: output-normed blocks / a dense lead layer before the experts "
            "(exaone_moe) / a layer of two sub-blocks (longcat_flash) decode through the v2 "
            "paged engine only")
    b, t = tokens.shape
    stream = _stream_active(c)
    embed = _maybe_stage(params["embed"]) if stream else params["embed"]
    x = _scale_embed(embed.astype(DTYPES[c.dtype])[tokens], c, DTYPES[c.dtype])
    if c.position == "learned":
        pe = _maybe_stage(params["pos_embed"]) if stream else params["pos_embed"]
        x = x + pe[positions]
    if c.embed_norm:
        x = _embed_norm(params, c, x, stream)

    def scan_body(x, inputs):
        lp, cache, local_flag = inputs
        if stream:
            lp = _stage_tree(lp)
        lp = _dequant_tree(lp, DTYPES[c.dtype])
        a = _norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        attn_out, new_cache = _attention_block(
            c, lp, a, positions, None, kv_cache=cache, local_flag=local_flag
        )
        if c.parallel_block:
            m = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
            mlp_out, _ = _mlp_block(c, lp, m)
            return x + attn_out + mlp_out, new_cache
        x = x + attn_out
        m = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        mlp_out, _ = _mlp_block(c, lp, m)
        return x + mlp_out, new_cache

    flags = jnp.asarray(
        c.attn_layer_pattern if c.attn_layer_pattern is not None else [1] * c.n_layers,
        jnp.int32,
    )
    x, new_caches = jax.lax.scan(scan_body, x, (params["layers"], kv_caches, flags))
    fn_w = _maybe_stage(params["final_norm"]) if stream else params["final_norm"]
    fn_b = params.get("final_norm_b")
    if stream and fn_b is not None:
        fn_b = _maybe_stage(fn_b)
    x = _norm(x, fn_w, fn_b, c.norm, c.norm_eps)
    return _apply_lm_head(params, x, c), new_caches


def init_kv_cache(config: TransformerConfig, batch: int, max_len: int):
    """Stacked per-layer KV cache pytree for decode_step."""
    c = config
    dtype = DTYPES[c.dtype]
    shape = (c.n_layers, batch, c.kv_heads, max_len, c.head_dim)
    return (
        jnp.zeros(shape, dtype),
        jnp.zeros(shape, dtype),
        jnp.zeros((c.n_layers,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def split_lm_batch(batch):
    """Normalize a causal-LM batch dict to (inputs, labels, loss_mask,
    positions, segment_ids); labels default to shifted input_ids."""
    tokens = batch["input_ids"]
    labels = batch.get("labels")
    mask = batch.get("loss_mask")
    if labels is None:
        labels = tokens[:, 1:]
        inputs = tokens[:, :-1]
        if mask is not None and mask.shape[1] == tokens.shape[1]:
            mask = mask[:, 1:]  # align with shifted labels
    else:
        inputs = tokens
    return inputs, labels, mask, batch.get("positions"), batch.get("segment_ids")


def embed_tokens(params, tokens, positions, config: TransformerConfig):
    """Embedding (+ learned positions, + bloom's embedding layernorm) — the
    model's stem, shared by the dense and pipelined paths."""
    x = _scale_embed(params["embed"].astype(DTYPES[config.dtype])[tokens], config, DTYPES[config.dtype])
    if config.position == "learned":
        pe = params["pos_embed"][positions]
        x = x + (pe[None] if positions.ndim == 1 else pe)
    if config.embed_norm:
        x = _embed_norm(params, config, x, stream=False)
    return x


def _masked_nll(logits, labels, mask):
    """Shared CE core: fp32 NLL → (sum_loss, count).

    log_softmax(x)[label] = x[label] - logsumexp(x): gathering the label
    logit + an fp32 logsumexp REDUCTION avoids materializing the [n, vocab]
    fp32 log-prob array the naive form writes and re-reads (~3 GB of HBM
    traffic per step at the bench shape; the cast fuses into the reduce)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ll = picked.astype(jnp.float32) - lse
    return jnp.sum(-ll * mask), jnp.sum(mask)


def nll_loss(logits, labels, mask=None):
    """Masked next-token NLL from full logits."""
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    total, count = _masked_nll(logits, labels, mask)
    return total / jnp.maximum(count, 1.0)


def lm_head_loss(params, x, labels, mask, config: TransformerConfig, aux=None):
    """Final norm → logits → masked NLL (+ MoE aux) — the model's head,
    shared by the dense and pipelined paths."""
    c = config
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), c.norm, c.norm_eps)
    logits = _apply_lm_head(params, x, c)
    loss = nll_loss(logits, labels, mask)
    if c.n_experts > 0 and aux is not None:
        loss = loss + c.moe_aux_loss_coef * aux
    return loss


def make_loss_fn(config: TransformerConfig):
    """Causal-LM loss over a batch dict {'input_ids': [b, s] (, 'labels',
    'loss_mask', 'segment_ids', 'positions')}. Next-token prediction; labels
    default to input_ids shifted. Matches the engine's loss_fn(params, batch)
    contract."""

    def loss_fn(params, batch):
        inputs, labels, mask, positions, segment_ids = split_lm_batch(batch)
        # fused/tiled heads feed the bare head matrix to the kernel — a biased
        # lm_head (phi) falls through to the dense path
        if (
            config.fused_ce
            and not config.lm_head_bias
            and not config.mlm_head  # the fused kernel has no MLM transform
            and on_tpu()
            and get_topology().world_size == 1
        ):
            # Pallas fused head+CE: logits never materialize in HBM
            # (ops/fused_ce.py). Single-device only: pallas_call is opaque to
            # GSPMD, and the head matmul wants the model-axis sharding on
            # multi-chip meshes.
            from deepspeed_tpu.ops.fused_ce import fused_ce_loss

            x, aux = forward_hidden(params, inputs, config, positions=positions, segment_ids=segment_ids)
            b, s, h = x.shape
            w = _lm_head_matrix(params, config, x.dtype)
            m = mask if mask is not None else jnp.ones(labels.shape, jnp.float32)
            # pad rows to the kernel's tile size: b*s is often 2^k - b (labels
            # shift drops one position), and a degenerate row block would
            # explode the Pallas grid
            n = b * s
            pad = (-n) % 256
            flat_x = x.reshape(n, h)
            flat_l = labels.reshape(-1)
            flat_m = m.reshape(-1)
            if pad:
                flat_x = jnp.concatenate([flat_x, jnp.zeros((pad, h), x.dtype)])
                flat_l = jnp.concatenate([flat_l, jnp.zeros((pad,), flat_l.dtype)])
                flat_m = jnp.concatenate([flat_m, jnp.zeros((pad,), flat_m.dtype)])
            per_row = fused_ce_loss(flat_x, w, flat_l)
            loss = jnp.sum(per_row * flat_m) / jnp.maximum(jnp.sum(flat_m), 1.0)
        elif config.loss_tiles > 1 and not config.lm_head_bias and not config.mlm_head:
            from deepspeed_tpu.parallel.sequence.tiled import tiled_logits_loss

            x, aux = forward_hidden(params, inputs, config, positions=positions, segment_ids=segment_ids)
            loss = tiled_logits_loss(
                _masked_nll,
                x,
                _lm_head_matrix(params, config, x.dtype),
                labels,
                num_tiles=config.loss_tiles,
                mask=mask,
            )
        else:
            logits, aux = forward(params, inputs, config, positions=positions, segment_ids=segment_ids)
            loss = nll_loss(logits, labels, mask)
        return loss + config.moe_aux_loss_coef * aux if config.n_experts > 0 else loss

    # the hybrid engine (train↔generate) recovers the architecture from the
    # loss fn — deepspeed.initialize only ever sees this callable
    loss_fn.model_config = config
    return loss_fn


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def flops_per_token(config: TransformerConfig, seq_len: Optional[int] = None) -> float:
    """Approximate training FLOPs per token (6ND rule + attention term)."""
    c = config
    s = seq_len or c.max_seq_len
    # vocab term counts the lm_head matmul once; the input embedding is a
    # gather, not a matmul, so tying does not change matmul FLOPs.
    n_dense = (
        c.hidden_size * (c.n_heads + 2 * c.kv_heads) * c.head_dim  # qkv
        + c.n_heads * c.head_dim * c.hidden_size  # out proj
        + c.hidden_size * c.ffn_dim * (3 if c.activation in ("swiglu", "geglu") else 2)
    ) * c.n_layers * c.sub_blocks + c.vocab_size * c.hidden_size  # (a moe_shortcut layer: two)
    # causal attention by the heads' width (n_heads * head_dim, which is not
    # hidden_size where the head size is decoupled, as in Qwen3-0.6B): the
    # count of benchmarks/harness/flops.py, held to it by tests/unit/test_models.py
    attn = 2 * c.n_layers * c.sub_blocks * s * c.n_heads * c.head_dim
    return 6.0 * (n_dense + attn / 2)
