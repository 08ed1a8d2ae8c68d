"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a configuration with a window pool
beside the block pool. Source: program counters. Blocks of the block pool (the GLOBAL layers')
held over the blocks it has, mean over the window's steps: driver.metrics.counters
``kv_global_blocks_used_total`` (summed a step) over ``engine_steps_total`` x the pool's blocks,
which are computed here as the program sizes them (``--kv-pool-bytes`` less the window layers'
rings, one a tracked sequence and a spare, over the bytes of a block over the global layers,
less the trash block). Near 100 the rows wait for blocks and the cell is bound by its cache.
Counted with tracing off or on; None where the program has no such counter (the parent) or the
configuration no ``layer_types``. Should move gen_tok_s."""
from benchmarks.harness.common import Catalog
from benchmarks.metrics.sat_kv_bytes_per_token import bytes as block_bytes
from benchmarks.metrics.sat_kv_bytes_per_token import layer_counts, window_delta


def ring_blocks(window, block_size):
    """Blocks of a window layer's ring: those a query still sees and the one being written."""
    return -(-(int(window) - 1) // block_size) + 1


def pool_blocks(hf, serve_args):
    """Blocks of the block pool under the cell's sizes."""
    bs = int(serve_args["--block-size"])
    n_global, n_window = layer_counts(hf)
    rings = (int(serve_args["--max-concurrent"]) + 1) * block_bytes(
        ring_blocks(hf["sliding_window"], bs), n_window, hf, bs)
    return (int(serve_args["--kv-pool-bytes"]) - rings) // block_bytes(1, n_global, hf, bs) - 1


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "kv_global_blocks_used_total" not in c1 or "layer_types" not in rec["hf"]:
        return None
    steps = window_delta(rec, "engine_steps_total")
    total = pool_blocks(rec["hf"], Catalog().cell(rec["cell"])["serve_args"])
    if steps <= 0 or total <= 0:
        return None
    return 100.0 * window_delta(rec, "kv_global_blocks_used_total") / (steps * total)
