"""Linear attention with a recurrent state: the gated delta rule (Gated
DeltaNet, arXiv:2412.06464) as Qwen3-Next's linear-attention layers compute it.
One token costs the same whatever the context's length: a sequence's cache is
a fixed ``[value heads, key dim, value dim]`` float32 state and the last
``conv width - 1`` inputs of a short causal convolution.
"""

from deepspeed_tpu.ops.linear_attention.gated_delta import (
    GDN_DECODE,
    causal_conv,
    gated_rms_norm,
    gdn_chunked,
    gdn_decode,
    gdn_gates,
    gdn_recurrent,
    l2norm,
)

__all__ = [
    "GDN_DECODE",
    "causal_conv",
    "gated_rms_norm",
    "gdn_chunked",
    "gdn_decode",
    "gdn_gates",
    "gdn_recurrent",
    "l2norm",
]
