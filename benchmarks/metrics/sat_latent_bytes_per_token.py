"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a latent-attention configuration.
Source: program counters. What a cached token costs in the pool, in bytes over the layers held:
the blocks the program reports its sequences hold (``latent_live_blocks_total``, a difference
over the window) priced by ``bytes()`` below, one plane of ``kv_lora_rank + qk_rope_head_dim`` bf16 a
token a layer, over the tokens those blocks hold. 5,760 for A.X-K1's five layers of 576, where
per-head keys and values (64 heads x (192 + 128)) would read 204,800: the ratio is why the
cell's contexts fit. It prices CAPACITY (a block's every token), not occupancy: how full the
blocks are is ``sat_latent_fill_pct``'s and the allocator's. Counted with tracing off or on; None
where the program counts no latent pool (the parent) or the configuration has no
``kv_lora_rank``. Should move gen_tok_s."""
from benchmarks.metrics.sat_kv_bytes_per_token import block_size_of, window_delta

ITEMSIZE = 2  # a bf16 pool


def bytes(blocks, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes of ``blocks`` pool blocks over the layers held: one latent plane."""
    width = int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
    return ITEMSIZE * blocks * int(hf["num_hidden_layers"]) * block_size * width


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "latent_live_blocks_total" not in c1 or "kv_lora_rank" not in rec["hf"]:
        return None
    blocks = window_delta(rec, "latent_live_blocks_total")
    if blocks <= 0:
        return None
    bs = block_size_of(rec)
    return bytes(blocks, rec["hf"], bs) / (blocks * bs)
