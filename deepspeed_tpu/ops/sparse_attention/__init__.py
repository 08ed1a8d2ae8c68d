"""Block-sparse attention (reference ``deepspeed/ops/sparse_attention/``).

Public surface parity: the sparsity configs, a ``SparseSelfAttention``
module-equivalent, and the functional kernel entries. The Triton
blocksparse matmul/softmax of the reference become Pallas kernels:

  * splash_pallas.py — the production path: masks (mask.py) compile into
    compacted per-q-block schedules (schedule.py) of active kv blocks and
    the kernel's grid covers ONLY those, via scalar prefetch;
  * sparse_pallas.py — the older layout-predicate kernel, kept as the
    ``reference`` oracle for parity tests (it visits every block and
    skips inactive ones under a cond).
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.sparse_attention.config import (
    BigBirdSparsityConfig,
    BSLongformerSparsityConfig,
    DenseSparsityConfig,
    FixedSparsityConfig,
    SparsityConfig,
    VariableSparsityConfig,
)
from deepspeed_tpu.ops.sparse_attention.mask import (
    CausalMask,
    DocumentMask,
    FullMask,
    LayoutMask,
    LocalMask,
    Mask,
    MultiHeadMask,
)
from deepspeed_tpu.ops.sparse_attention.schedule import (
    BlockSchedule,
    build_schedule,
    schedule_from_layout,
    schedule_from_mask,
)
from deepspeed_tpu.ops.sparse_attention.sparse_pallas import (
    sparse_attention,
    sparse_attention_reference,
    sparse_attention_with_bias,
)
from deepspeed_tpu.ops.sparse_attention.splash_pallas import (
    splash_attention,
)


class SparseSelfAttention:
    """Functional analogue of the reference ``SparseSelfAttention`` module
    (``sparse_self_attention.py``): holds a sparsity config, builds/caches
    the compacted block schedule per sequence length, and applies the
    scheduled splash kernel (``use_splash=False`` drops back to the
    layout-predicate oracle kernel).

    ``__call__(q, k, v)`` with [b, h, s, d] tensors; GQA kv runs natively
    in the splash kernel (index maps fold the head group — kv is never
    replicated), and is expanded only on the oracle path.
    """

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 key_padding_mask_mode: str = "add", attn_mask_mode: str = "mul",
                 max_seq_length: int = 2048, interpret: bool = False,
                 use_splash: bool = True):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(num_heads=4)
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self.max_seq_length = max_seq_length
        self.interpret = interpret
        self.use_splash = use_splash
        self._layouts = {}
        self._schedules = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def get_schedule(self, seq_len: int) -> BlockSchedule:
        # cached: the schedule is a trace-time constant, rebuilt only per
        # new sequence length — never per step
        if seq_len not in self._schedules:
            self._schedules[seq_len] = self.sparsity_config.make_schedule(seq_len)
        return self._schedules[seq_len]

    def __call__(self, query, key, value, rpe=None, key_padding_mask=None, attn_mask=None):
        b, h, s, d = query.shape
        if h != self.sparsity_config.num_heads:
            raise ValueError(f"query has {h} heads, sparsity config expects "
                             f"{self.sparsity_config.num_heads}")
        causal = getattr(self.sparsity_config, "attention",
                         "bidirectional") == "unidirectional"
        if rpe is not None or key_padding_mask is not None or attn_mask is not None:
            # masked variants fall back to the dense biased path (reference
            # applies these inside the softmax kernel: softmax.py rpe/
            # key_padding_mask/attn_mask args)
            h_kv = key.shape[1]
            if h_kv != h:
                key = jnp.repeat(key, h // h_kv, axis=1)
                value = jnp.repeat(value, h // h_kv, axis=1)
            bias = jnp.zeros((1, 1, s, s), jnp.float32)
            if rpe is not None:
                bias = bias + rpe.astype(jnp.float32)
            if key_padding_mask is not None:  # [b, s] over keys
                kpm = key_padding_mask.astype(jnp.float32)
                if self.key_padding_mask_mode == "add":
                    bias = bias + kpm[:, None, None, :]
                else:  # "mul": 0 = masked
                    bias = bias + jnp.where(kpm[:, None, None, :] != 0, 0.0, -1e30)
            if attn_mask is not None:  # [s, s] (or broadcastable)
                am = attn_mask.astype(jnp.float32)
                am = am[None, None] if am.ndim == 2 else am
                if self.attn_mask_mode == "add":
                    bias = bias + am
                else:
                    bias = bias + jnp.where(am != 0, 0.0, -1e30)
            return sparse_attention_with_bias(
                query, key, value, jnp.asarray(self.get_layout(s)),
                self.sparsity_config.block, causal=causal, bias=bias,
            )
        if self.use_splash:
            return splash_attention(
                query, key, value, self.get_schedule(s),
                interpret=self.interpret or None,
            )
        h_kv = key.shape[1]
        if h_kv != h:
            key = jnp.repeat(key, h // h_kv, axis=1)
            value = jnp.repeat(value, h // h_kv, axis=1)
        return sparse_attention(
            query, key, value, self.get_layout(s), self.sparsity_config.block,
            causal=causal, interpret=self.interpret,
        )


__all__ = [
    "SparsityConfig",
    "DenseSparsityConfig",
    "FixedSparsityConfig",
    "BSLongformerSparsityConfig",
    "BigBirdSparsityConfig",
    "VariableSparsityConfig",
    "SparseSelfAttention",
    "Mask",
    "FullMask",
    "CausalMask",
    "LocalMask",
    "DocumentMask",
    "LayoutMask",
    "MultiHeadMask",
    "BlockSchedule",
    "build_schedule",
    "schedule_from_mask",
    "schedule_from_layout",
    "sparse_attention",
    "sparse_attention_reference",
    "sparse_attention_with_bias",
    "splash_attention",
]
