"""Layer: device, train cells. memory_stats()["peak_bytes_in_use"], the highest
over the cell's devices, in GB (10^9). Room for batch or cache: should move train_tok_s."""


def read(rec):
    if not rec.get("peak_bytes"):
        return None
    return rec["peak_bytes"] / 1e9
