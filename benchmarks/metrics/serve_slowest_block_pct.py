"""Layer: load generator (the benchmark's own loop and its host). How much longer
the slowest block of the window took than the median block, per delivered
token: a block that carries more prefill reads some percent, a host that stood
still for a second tens. It is what the median in gen_tok_s leaves out."""
from benchmarks.harness import stats


def read(rec):
    return stats.slowest_block_pct(rec["marks"]) if rec.get("marks") else None
