"""Layer: kernels (ops/attention/paged_pallas.py), serve cells. Source: program counters. Blocks
the decode rows' contexts cover over the slots of their block tables: driver.metrics.counters
``paged_live_blocks_total`` (``ceil(pool tokens / block size)`` summed over a step's decode rows)
over ``paged_table_slots_total`` (rows x ``--max-blocks-per-seq``), as differences over the
window, in percent. What share of a 32-wide table the traffic fills: a kernel that walks the
table does the other share for nothing. Counted with tracing off or on; None where the program
has no such counters. Should move tpot_p50_ms."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "paged_live_blocks_total", "paged_table_slots_total")
