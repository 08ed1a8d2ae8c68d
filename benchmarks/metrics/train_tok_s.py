"""End to end, train cells: tokens trained per second, all chips together, in the
median block of the window (a block is block_steps steps, from one loss seen
ready to another; harness/stats.py says why the median and not the total)."""
from benchmarks.harness import stats


def read(rec):
    return stats.median_rate(rec["marks"])
