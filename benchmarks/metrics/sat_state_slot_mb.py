"""Layer: cache (inference/v2/kv_pool.py, ragged_manager.py), a configuration with recurrent
layers. Source: program counters. What one tracked sequence's state slot costs over the
recurrent layers, whatever its length, in MB (10**6 bytes): ``state_slot_bytes`` of the
counters' snapshot as the window closes, which the driver sets once from the engine's own
accounting (``kv_pool_info`` -> ``state_bytes_per_slot``: float32 states and the conv's carried
inputs in the compute dtype over the layers of the kind). 9.32 at Jamba2-3B's widths, beside
1 KiB of K/V a token (the block pool's bytes a block over its tokens:
``sat_kv_bytes_per_token`` reads that where a configuration has ``layer_types``): the two
numbers a deployment's best batch size turns on, and what a change to the slot's layout or
dtype moves. Counted with tracing off or on; None where the program has no such counter (the
parent) or the model no state slot (0). Should move gen_tok_s."""


def read(rec):
    held = rec["snapshots"][1]["counters"].get("state_slot_bytes", 0)
    return held / 1e6 if held else None
