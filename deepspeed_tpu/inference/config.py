"""Inference configs.

Reference: ``DeepSpeedInferenceConfig`` (inference/config.py — dtype,
tensor_parallel.tp_size, replace_with_kernel_inject, max_out_tokens, ...)
and ``RaggedInferenceEngineConfig`` (inference/v2/config_v2.py — state
manager + memory config for FastGen).
"""

from dataclasses import dataclass, field
from typing import Optional

from deepspeed_tpu.runtime.config_utils import DSConfigModel, submodel


@dataclass
class TPConfig(DSConfigModel):
    tp_size: int = 1
    enabled: bool = True


@dataclass
class QuantConfig(DSConfigModel):
    """Weight-only quantized inference (reference inference/quantization/)."""

    enabled: bool = False
    bits: int = 8  # 8 | 4 (packed)
    group_size: int = 128


@dataclass
class DeepSpeedInferenceConfig(DSConfigModel):
    """v1 engine config (reference inference/config.py)."""

    dtype: str = "bfloat16"
    tensor_parallel: Optional[TPConfig] = submodel(TPConfig)
    quant: QuantConfig = submodel(QuantConfig)
    max_out_tokens: int = 1024
    min_out_tokens: int = 1
    max_tokens: int = 4096  # prompt + generation budget
    replace_with_kernel_inject: bool = True  # flash/fused kernels on TPU
    enable_cuda_graph: bool = False  # [compat] jit IS the graph on TPU
    checkpoint: Optional[str] = None
    zero_inference: bool = False
    temperature: float = 1.0
    top_k: int = 0
    greedy: bool = True
    # > 1: generate() fuses this many decode iterations into one device
    # program (sampled token fed back in-device), so the host round trip
    # between two programs is paid once per decode_steps tokens;
    # output-identical to per-step decoding
    decode_steps: int = 1

    @classmethod
    def from_dict(cls, d=None, strict: bool = False):
        d = dict(d or {})
        tp = d.get("tensor_parallel")
        if isinstance(tp, int):  # convenience: tensor_parallel: 4
            d["tensor_parallel"] = {"tp_size": tp}
        if "dtype" in d and not isinstance(d["dtype"], str):
            d["dtype"] = str(d["dtype"]).replace("torch.", "").replace("jnp.", "")
        return super().from_dict(d, strict=strict)


@dataclass
class KVCacheConfig(DSConfigModel):
    block_size: int = 128  # tokens per KV block (reference v2 kv block)
    num_blocks: int = 256
    max_blocks_per_seq: int = 32
    # automatic prefix caching: full prompt blocks are kept in a token-trie
    # after prefill and shared (refcounted) with later requests whose
    # prompts start with the same block-aligned tokens — a hit skips that
    # much prefill. Off by default at the engine level so plain generate()
    # keeps its exact allocation behavior; the serving stack (dstpu serve,
    # bench --serving-load) turns it on unless told otherwise. Outputs are
    # bit-identical either way.
    prefix_cache: bool = False
    # cap on trie-held blocks (0 = bounded only by the pool); evicting is
    # LRU over cached blocks no live sequence shares
    prefix_cache_blocks: int = 0
    # pool payload dtype: "bf16" stores blocks in the engine compute dtype;
    # "int8" stores quantized payloads + a per-vector fp32 scale plane
    # (block_quant.quantize_kv) — roughly half the HBM per block, so ~2x
    # blocks (admission / prefix-cache capacity) at a fixed byte budget.
    # Dequantization happens inside the attention read (in-kernel on TPU).
    kv_cache_dtype: str = "bf16"
    # host-memory block tier behind the prefix trie (host_tier.py): > 0
    # bounds a pinned-host LRU of evicted prefix blocks at this many
    # bytes; trie misses that hit the tier re-import through the donated
    # KV scatter instead of re-prefilling. 0 disables. Requires
    # prefix_cache; payloads are stored as exported, so an int8 pool's
    # tier holds ~2x the blocks per byte. Outputs are bit-identical
    # tier on vs off.
    host_tier_bytes: int = 0
    # blocks per window of the double-buffered chunked re-import
    # (engine_v2.import_kv_blocks_chunked); one fixed window shape keeps
    # the donated scatter at zero steady-state recompiles
    host_tier_chunk_blocks: int = 8


@dataclass
class StateManagerConfig(DSConfigModel):
    """Reference DSStateManagerConfig (inference/v2/ragged/manager_configs.py)."""

    max_tracked_sequences: int = 64
    max_ragged_batch_size: int = 512  # token budget per engine step
    max_ragged_sequence_count: int = 16
    max_context: int = 4096


@dataclass
class RaggedInferenceEngineConfig(DSConfigModel):
    """v2 (FastGen) engine config (reference inference/v2/config_v2.py)."""

    dtype: str = "bfloat16"
    tp_size: int = 1
    # split-phase step grid (0 = derive from the token budget): each engine
    # step serves <= max_prompt_chunks prompt chunks of <= prompt_chunk
    # tokens alongside the full decode row set — the static-shape re-think
    # of Dynamic SplitFuse packing (a handful of compiled shapes instead of
    # one per ragged total)
    prompt_chunk: int = 0
    max_prompt_chunks: int = 0
    # sampling (reference FastGen serves sampled decoding via MII on top of
    # v2 logits; v1 parity knobs). greedy/top_k/top_p are STATIC — they
    # shape the compiled programs; change them via engine.set_sampling()
    greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    # speculative decoding (serving/spec/): > 0 enables draft-and-verify
    # decode rounds of up to spec_k draft tokens per sequence per step.
    # spec_k is static per compiled verify program (one program per K);
    # output is bit-identical to spec_k=0 — this is purely a latency knob.
    spec_k: int = 0
    # n-gram order cap for the default model-free draft proposer
    spec_ngram: int = 3
    # decode-attention implementation: "auto" resolves to the Pallas paged
    # kernel on TPU (kernel-tiled head dims, tp_size=1) and the dense XLA
    # gather elsewhere; "kernel"/"dense" force a path; anything else raises
    # at engine construction (no silent fallback)
    paged_attention_impl: str = "auto"
    # quantized collectives for the TP decode step (comm/quantized.py):
    # "int8" runs the MODEL_AXIS psum behind the attention-output and MLP
    # down projections as an int8 reduce-scatter + re-quantized int8
    # all-gather (EQuARX-style, inside an explicit shard_map island);
    # "none" keeps the implicit full-width GSPMD psum. No-op at tp_size=1;
    # anything else raises at engine construction.
    comm_quant: str = "none"
    # tile-granular compute/collective overlap (comm/overlap_tiled.py):
    # "tiled" decomposes each TP row wire (attention-output / MLP down
    # psum) into tp_overlap_tiles independent per-tile reduce-scatter→
    # all-gather ppermute rings — peers the latency-hiding scheduler can
    # interleave with compute; comm_quant's int8 payload+scale planes ride
    # the same tiles. "none" keeps the monolithic wire. No-op at tp_size=1;
    # shapes the tile constraint rejects fall back to untiled (same
    # numerics); anything else raises at engine construction.
    comm_overlap: str = "none"
    # per-wire tile count for comm_overlap="tiled" (>= 1)
    tp_overlap_tiles: int = 4
    quant: QuantConfig = submodel(QuantConfig)
    kv_cache: Optional[KVCacheConfig] = submodel(KVCacheConfig)
    state_manager: Optional[StateManagerConfig] = submodel(StateManagerConfig)
