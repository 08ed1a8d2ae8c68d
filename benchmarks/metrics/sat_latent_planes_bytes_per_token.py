"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a latent-attention configuration
whose layer caches TWO planes (``num_layers`` layers of two latent attentions: longcat_flash).
Source: program counters. What a cached token costs in the pool, in bytes over the planes held:
the blocks the program reports its sequences hold (``latent_live_blocks_total``, a difference
over the window: blocks of a TABLE, each standing for its ``2 x num_layers`` planes) priced by
``bytes()`` below, one vector of ``kv_lora_rank + qk_rope_head_dim`` bf16 a token a PLANE, over
the tokens those blocks hold. 9,216 for four layers of two planes of 576 (A.X-K1's five
layers of one: 5,760). It prices CAPACITY (a block's every token), not occupancy:
``sat_latent_planes_fill_pct`` has that. Counted with tracing off or on; None where the program
counts no latent pool or the configuration has no ``num_layers``. Should move gen_tok_s."""
from benchmarks.metrics.sat_kv_bytes_per_token import block_size_of, window_delta
from benchmarks.metrics.sat_mla_planes_decode_roofline_pct import planes

ITEMSIZE = 2  # a bf16 pool


def bytes(blocks, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes of ``blocks`` pool blocks over the planes held: one latent vector a token each."""
    width = int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
    return ITEMSIZE * blocks * planes(hf) * block_size * width


def read(rec):
    c1, hf = rec["snapshots"][1]["counters"], rec["hf"]
    if "latent_live_blocks_total" not in c1 or "num_layers" not in hf or "kv_lora_rank" not in hf:
        return None
    blocks = window_delta(rec, "latent_live_blocks_total")
    if blocks <= 0:
        return None
    bs = block_size_of(rec)
    return bytes(blocks, hf, bs) / (blocks * bs)
