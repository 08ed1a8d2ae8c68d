"""Layer: kernels (ops/attention/paged_pallas.py, kernel ``dstpu_paged_decode``), a
configuration whose stack mixes window and global layers. Source: program counters. Blocks a
WINDOW layer's decode walk visits over what the same rows' GLOBAL walk visits:
driver.metrics.counters ``paged_window_live_blocks_total`` (for a decode row at position p, the
blocks that hold keys p - window + 1 .. p - 1: one or two at a window of a block) over
``paged_live_blocks_total`` (``ceil(pool tokens / block size)`` of the same rows), as
differences over the window, in percent. The kernel's walk is bounded by the window, so a
window layer's call costs this share of a global layer's. Counted with tracing off or on; None
where the program has no such counter (the parent). Should move gen_tok_s."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "paged_window_live_blocks_total", "paged_live_blocks_total")
