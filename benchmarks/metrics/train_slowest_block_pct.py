"""Layer: load generator (the benchmark's own loop and its host). How much longer
the slowest block of the window took than the median block, per token: reads
near 0 on a quiet host, and a host that stood still for a second reads tens of
percent. It is what the median in train_tok_s leaves out."""
from benchmarks.harness import stats


def read(rec):
    return stats.slowest_block_pct(rec["marks"])
