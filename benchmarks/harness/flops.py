"""Operations a decoder-only transformer needs, computed from the published
sizes in a configuration file (Hugging Face ``config.json`` keys). Kept with
the benchmark so that a PR to the program cannot change what "one token" costs.

Counted: the matrix multiplications of the layers and of the output head, and
causal attention with ``num_attention_heads * head_dim`` (not ``hidden_size``:
the two differ wherever the head size is decoupled, as in Qwen3-0.6B). Not
counted: the embedding gather, norms, RoPE, softmax, and anything a
rematerialisation policy computes twice."""


def _head_dim(hf: dict) -> int:
    return int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])


def matmul_params(hf: dict) -> int:
    """Weights that take part in a matrix multiplication for every token: the
    layers' projections and the output head (tied or not, it multiplies)."""
    h, d = hf["hidden_size"], _head_dim(hf)
    nh = hf["num_attention_heads"]
    nkv = hf.get("num_key_value_heads") or nh
    per_layer = (
        h * (nh + 2 * nkv) * d          # q, k, v
        + nh * d * h                    # o
        + 3 * h * hf["intermediate_size"]  # gate, up, down (SwiGLU)
    )
    return per_layer * hf["num_hidden_layers"] + hf["vocab_size"] * h


def forward_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward pass, one token of a ``seq_len`` causal sequence, averaged over
    the sequence: a query sees seq_len / 2 keys on average, and pays one
    multiply-add each for the score and for the weighted value."""
    attn = 2 * 2 * hf["num_attention_heads"] * _head_dim(hf) * (seq_len / 2)
    return 2.0 * matmul_params(hf) + attn * hf["num_hidden_layers"]


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    """Forward and backward: the backward pass costs twice the forward."""
    return 3.0 * forward_flops_per_token(hf, seq_len)


def param_count(hf: dict) -> int:
    """Parameters held, norms included; an untied head counts twice."""
    h, d = hf["hidden_size"], _head_dim(hf)
    norms = hf["num_hidden_layers"] * (2 * h + 2 * d) + h
    untied = 0 if hf.get("tie_word_embeddings") else hf["vocab_size"] * h
    return matmul_params(hf) + norms + untied


def kv_bytes_per_token(hf: dict, bytes_per_value: int = 2) -> int:
    nkv = hf.get("num_key_value_heads") or hf["num_attention_heads"]
    return 2 * hf["num_hidden_layers"] * nkv * _head_dim(hf) * bytes_per_value
