"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a latent-attention configuration
whose layer caches TWO planes (longcat_flash). Source: program counters. Blocks of the latent pool
the tracked sequences hold over the blocks it has, mean over the window's steps:
``latent_live_blocks_total`` (the blocks the sequences' tables name, summed a step) over
``engine_steps_total`` x the pool's blocks, which are computed here as the program sizes them:
``--kv-pool-bytes`` over the bytes of a block over its ``2 x num_layers`` planes
(``sat_latent_planes_bytes_per_token.bytes``), less the trash block. Near 100 the rows wait for
blocks and the cell is bound by its cache; well under it, by the rows a step carries. Counted
with tracing off or on; None where the program counts no latent pool or the configuration has no
``num_layers``. Should move gen_tok_s."""
from benchmarks.harness.common import Catalog
from benchmarks.metrics.sat_kv_bytes_per_token import window_delta
from benchmarks.metrics.sat_latent_planes_bytes_per_token import bytes as block_bytes


def pool_blocks(hf, serve_args):
    """Blocks of the latent pool under the cell's sizes."""
    return int(serve_args["--kv-pool-bytes"]) // block_bytes(
        1, hf, int(serve_args["--block-size"])) - 1


def read(rec):
    c1, hf = rec["snapshots"][1]["counters"], rec["hf"]
    if "latent_live_blocks_total" not in c1 or "num_layers" not in hf or "kv_lora_rank" not in hf:
        return None
    steps = window_delta(rec, "engine_steps_total")
    total = pool_blocks(hf, Catalog().cell(rec["cell"])["serve_args"])
    if steps <= 0 or total <= 0:
        return None
    return 100.0 * window_delta(rec, "latent_live_blocks_total") / (steps * total)
