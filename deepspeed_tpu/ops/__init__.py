"""TPU op layer (reference deepspeed/ops/ + op_builder/).

Each op family ships a Pallas TPU kernel plus a jnp reference fallback and is
registered in the OpBuilder registry so ``get_accelerator().create_op_builder``
resolves them like the reference's JIT-compiled CUDA ops.
"""

from deepspeed_tpu.ops.op_builder import ALL_OPS, OpBuilder, PallasOpBuilder, register_op


@register_op
class FlashAttnBuilder(PallasOpBuilder):
    NAME = "flash_attn"

    def _build(self):
        from deepspeed_tpu.ops.attention import attention

        return attention


@register_op
class FusedAdamBuilder(PallasOpBuilder):
    NAME = "fused_adam"

    def _build(self):
        from deepspeed_tpu.ops.adam import FusedAdam

        return FusedAdam


@register_op
class QuantizerBuilder(PallasOpBuilder):
    NAME = "quantizer"

    def _build(self):
        from deepspeed_tpu.ops import quantizer

        return quantizer


@register_op
class FusedRMSNormBuilder(PallasOpBuilder):
    NAME = "rms_norm"

    def _build(self):
        # mesh-aware entry: per-shard Pallas under multi-device topologies
        # (the raw fused_rms_norm kernel is GSPMD-opaque)
        from deepspeed_tpu.ops.normalization import rms_norm

        return rms_norm


@register_op
class SparseAttnBuilder(PallasOpBuilder):
    NAME = "sparse_attn"

    def _build(self):
        # importlib: the package attribute `sparse_attention` is rebound to
        # the kernel *function* by the re-export block below — the builder
        # hands out the module (reference parity: sparse_attn is a package)
        import importlib

        return importlib.import_module("deepspeed_tpu.ops.sparse_attention")


@register_op
class EvoformerAttnBuilder(PallasOpBuilder):
    NAME = "evoformer_attn"

    def _build(self):
        from deepspeed_tpu.ops.deepspeed4science import DS4Sci_EvoformerAttention

        return DS4Sci_EvoformerAttention


@register_op
class SpatialInferenceBuilder(PallasOpBuilder):
    NAME = "spatial_inference"

    def _build(self):
        from deepspeed_tpu.ops import spatial

        return spatial


@register_op
class RandomLTDBuilder(PallasOpBuilder):
    NAME = "random_ltd"

    def _build(self):
        from deepspeed_tpu.ops import random_ltd

        return random_ltd


@register_op
class FPQuantizerBuilder(PallasOpBuilder):
    NAME = "fp_quantizer"

    def _build(self):
        from deepspeed_tpu.ops.quantizer import block_quant

        return block_quant


# Native (C++ host) ops register themselves on import of their modules.
from deepspeed_tpu.ops import aio as _aio  # noqa: F401  (registers async_io)
from deepspeed_tpu.ops.adam import cpu_adam as _cpu_adam  # noqa: F401  (registers cpu_adam)

# Sparse attention is a first-class export, not just a builder target:
# the scheduled splash kernel + its mask/schedule surface (reference
# exposes these as deepspeed.ops.sparse_attention.*).
from deepspeed_tpu.ops.sparse_attention import (  # noqa: F401
    BigBirdSparsityConfig,
    BlockSchedule,
    BSLongformerSparsityConfig,
    CausalMask,
    DenseSparsityConfig,
    DocumentMask,
    FixedSparsityConfig,
    LocalMask,
    MultiHeadMask,
    SparseSelfAttention,
    SparsityConfig,
    VariableSparsityConfig,
    schedule_from_layout,
    schedule_from_mask,
    sparse_attention,
    sparse_attention_reference,
    splash_attention,
)

# Compatibility table (reference deepspeed.ops.__compatible_ops__)
__compatible_ops__ = {name: True for name in ALL_OPS}
