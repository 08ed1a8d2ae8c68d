"""Attention ops: TPU flash attention (Pallas) with a jnp reference fallback.

TPU-native replacement for the reference's attention kernel zoo
(csrc/transformer/inference softmax/attention kernels, evoformer_attn,
blocked/flash attention in inference/v2/kernels/ragged_ops). One public
entry point ``attention`` dispatches to the best implementation for the
platform; numerics are validated against ``mha_reference`` in
tests/unit/ops/test_attention.py.
"""

from deepspeed_tpu.ops.attention.core import attention, mha_reference
from deepspeed_tpu.ops.attention.flash_pallas import (
    head_major,
    heads_flat,
    heads_view,
    token_major,
    tokens_view,
)
from deepspeed_tpu.ops.attention.sharded import (
    head_sharded_flash,
    ring_flash_attention,
)

__all__ = [
    "attention",
    "head_major",
    "head_sharded_flash",
    "heads_flat",
    "heads_view",
    "mha_reference",
    "ring_flash_attention",
    "token_major",
    "tokens_view",
]
