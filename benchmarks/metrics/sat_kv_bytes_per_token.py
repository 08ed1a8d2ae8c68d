"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a configuration whose stack mixes
window and global attention layers. Source: program counters. Cache bytes in use over context
tokens tracked, mean over the window's steps: driver.metrics.counters
``kv_global_blocks_used_total`` (blocks of the block pool held, summed a step) x the bytes of a
block over the GLOBAL layers + ``kv_window_blocks_used_total`` (ring blocks of the window pool
held: a tracked sequence's rings, whatever its context) x the bytes of a block over the WINDOW
layers, over ``kv_context_tokens_total`` (tokens of context the tracked sequences hold), as
differences over the window, in bytes a token. ``bytes()`` below counts a block. A pool that kept
every layer's K/V for every token would read ``layers x 4 KiB`` here (32 KiB at 8 layers of 8 KV
heads of 128); a window pool reads the global layers' share plus the rings over the context.
Counted with tracing off or on; None where the program has no such counters (the parent) or the
configuration no ``layer_types``. Should move gen_tok_s."""
from benchmarks.harness.common import Catalog

ITEMSIZE = 2  # a bf16 pool


def layer_counts(hf):
    """(global layers, window layers) of the layers held: the head of ``layer_types``."""
    kinds = list(hf["layer_types"])[: int(hf["num_hidden_layers"])]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def bytes(blocks, layers, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes of K and V in ``blocks`` pool blocks over ``layers`` layers."""
    head_dim = hf.get("head_dim") or int(hf["hidden_size"]) // int(hf["num_attention_heads"])
    return 2 * ITEMSIZE * blocks * layers * block_size * int(hf["num_key_value_heads"]) * int(head_dim)


def block_size_of(rec):
    return int(Catalog().cell(rec["cell"])["serve_args"]["--block-size"])


def window_delta(rec, name):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    return c1[name] - c0.get(name, 0)


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "kv_context_tokens_total" not in c1 or "layer_types" not in rec["hf"]:
        return None
    tokens = window_delta(rec, "kv_context_tokens_total")
    if tokens <= 0:
        return None
    n_global, n_window = layer_counts(rec["hf"])
    bs = block_size_of(rec)
    held = (bytes(window_delta(rec, "kv_global_blocks_used_total"), n_global, rec["hf"], bs)
            + bytes(window_delta(rec, "kv_window_blocks_used_total"), n_window, rec["hf"], bs))
    return held / tokens
