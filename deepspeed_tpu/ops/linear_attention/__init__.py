"""Linear attention with a recurrent state: the gated delta rule (Gated
DeltaNet, arXiv:2412.06464) as Qwen3-Next's linear-attention layers compute it.
One token costs the same whatever the context's length: a sequence's cache is
a fixed ``[value heads, key dim, value dim]`` float32 state and the last
``conv width - 1`` inputs of a short causal convolution. ``kda.py``: the same
rule with a decay for every key channel (Kimi Delta Attention).
``delta_chunk.py``: a prompt chunk of either rule as one kernel.
"""

from deepspeed_tpu.ops.linear_attention.delta_chunk import GDN_CHUNK, KDA_CHUNK
from deepspeed_tpu.ops.linear_attention.gated_delta import (
    GDN_DECODE,
    KDA_DECODE,
    causal_conv,
    gated_rms_norm,
    gdn_chunked,
    gdn_decode,
    gdn_gates,
    gdn_recurrent,
    l2norm,
)
from deepspeed_tpu.ops.linear_attention.kda import kda_chunked, kda_decode, kda_recurrent

__all__ = [
    "GDN_CHUNK",
    "GDN_DECODE",
    "KDA_CHUNK",
    "KDA_DECODE",
    "causal_conv",
    "gated_rms_norm",
    "gdn_chunked",
    "gdn_decode",
    "gdn_gates",
    "gdn_recurrent",
    "kda_chunked",
    "kda_decode",
    "kda_recurrent",
    "l2norm",
]
