"""Selective state-space layers (Mamba-1, arXiv:2312.00752) as Jamba's Mamba
layers compute them. One token costs the same whatever the context's length: a
sequence's cache is a fixed ``[state size, channels]`` float32 state and the
last ``conv width - 1`` inputs of a short causal convolution.
"""

from deepspeed_tpu.ops.state_space.mamba import (
    MAMBA_DECODE,
    MAMBA_SCAN,
    mamba_decode,
    mamba_recurrent,
    mamba_scan,
    state_shape,
)

__all__ = [
    "MAMBA_DECODE",
    "MAMBA_SCAN",
    "mamba_decode",
    "mamba_recurrent",
    "mamba_scan",
    "state_shape",
]
