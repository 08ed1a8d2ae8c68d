"""Layer: collectives (runtime/zero, comm/). Source: device trace. Time inside
all-gather, reduce-scatter, all-reduce and collective-permute operations
during which no other operation ran on device 0, over the traced window.
Should move train_tok_s in the cells that shard over chips."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["window_s"]
