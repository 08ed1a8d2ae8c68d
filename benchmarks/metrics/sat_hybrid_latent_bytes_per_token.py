"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a configuration whose latent
attention layers sit in a stack with recurrent layers. Source: program counters. What a cached
token costs in the block pool, in bytes over the LATENT layers alone: the blocks the program
reports its sequences hold (``latent_live_blocks_total``, a difference over the window) priced
by ``bytes()`` below, one plane of ``kv_lora_rank + qk_rope_head_dim`` bf16 a token a layer of
``linear_attn_config.full_attn_layers``, over the tokens those blocks hold. 3,456 for Kimi
Linear's three planes of 576 (``sat_latent_bytes_per_token`` multiplies by
``num_hidden_layers``, 12 there, and would read four times too high): the number beside
``sat_state_slot_mb`` (19.54: the other nine layers' state, whatever the length) on which the
best batch turns. It prices CAPACITY (a block's every token), not occupancy. Counted with
tracing off or on; None where the program counts no latent pool beside state slots (the
parent) or the configuration has no ``linear_attn_config``. Should move gen_tok_s."""
from benchmarks.metrics.sat_kv_bytes_per_token import block_size_of, window_delta

ITEMSIZE = 2  # a bf16 pool


def layers(hf):
    """Latent attention layers of the configuration: the planes of its pool."""
    return len(hf["linear_attn_config"]["full_attn_layers"])


def bytes(blocks, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes of ``blocks`` pool blocks over the latent layers: one plane each."""
    width = int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
    return ITEMSIZE * blocks * layers(hf) * block_size * width


def read(rec):
    c1, hf = rec["snapshots"][1]["counters"], rec["hf"]
    if ("latent_live_blocks_total" not in c1 or not c1.get("state_slot_bytes")
            or "linear_attn_config" not in hf or "kv_lora_rank" not in hf):
        return None
    blocks = window_delta(rec, "latent_live_blocks_total")
    if blocks <= 0:
        return None
    bs = block_size_of(rec)
    return bytes(blocks, hf, bs) / (blocks * bs)
