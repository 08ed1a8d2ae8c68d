"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a latent-attention configuration.
Source: program counters. Blocks of the latent pool the tracked sequences hold over the blocks it
has, mean over the window's steps: driver.metrics.counters ``latent_live_blocks_total`` (the
blocks the sequences' tables name, summed a step; what the prefix cache alone retains until a
request needs the room is not among them: ``kv_global_blocks_used_total`` counts that too and
reads 100 in a pool that has been full once) over ``engine_steps_total`` x the pool's blocks,
which are computed here as the program sizes them (``--kv-pool-bytes`` over the bytes of a block over the layers held, less the trash block). Near
100 the rows wait for blocks and the cell is bound by its cache; well under it, by the rows a
step carries. Counted with tracing off or on; None where the program counts no latent pool (the
parent) or the configuration has no ``kv_lora_rank``. Should move gen_tok_s."""
from benchmarks.harness.common import Catalog
from benchmarks.metrics.sat_kv_bytes_per_token import window_delta
from benchmarks.metrics.sat_latent_bytes_per_token import bytes as block_bytes


def pool_blocks(hf, serve_args):
    """Blocks of the latent pool under the cell's sizes."""
    return int(serve_args["--kv-pool-bytes"]) // block_bytes(
        1, hf, int(serve_args["--block-size"])) - 1


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "latent_live_blocks_total" not in c1 or "kv_lora_rank" not in rec["hf"]:
        return None
    steps = window_delta(rec, "engine_steps_total")
    total = pool_blocks(rec["hf"], Catalog().cell(rec["cell"])["serve_args"])
    if steps <= 0 or total <= 0:
        return None
    return 100.0 * window_delta(rec, "latent_live_blocks_total") / (steps * total)
