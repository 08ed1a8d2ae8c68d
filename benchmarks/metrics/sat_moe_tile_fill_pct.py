"""Layer: expert layer (parallel/moe/grouped.py). Rows the expert layers had to multiply over
rows their kernel's tiles covered: driver.metrics.counters ``moe_routed_rows_total`` (live
(token, expert) pairs; the grid's padding is routed nowhere) over ``moe_computed_rows_total``
(the tile's rows for every visit of a tile by a group), as differences over the window, in
percent. 4 rows an expert under tiles of 16 cannot pass 25. Counted with tracing off or on;
None where the program has no such counters. Should move gen_tok_s."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "moe_routed_rows_total", "moe_computed_rows_total")
