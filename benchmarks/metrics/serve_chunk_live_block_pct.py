"""Layer: kernels (ops/attention/paged_pallas.py), serve cells. Source: program counters. Key
blocks the prompt chunks' rows hold over the slots a walk of whole tables covers:
driver.metrics.counters ``chunk_live_blocks_total`` (for each chunk row of a step, ``ceil(chunk
start / block size)`` pool blocks below the chunk + ``ceil(tokens / block size)`` of its own) over
``chunk_table_slots_total`` (chunk rows of the grid x (``--max-blocks-per-seq`` + chunk length /
block size): what the dense chunk attention gathers and scores), as differences over the
window, in percent. What share of the dense walk is work: the kernel ``dstpu_paged_chunk`` runs a
program a live block a tile of queries. Counted with tracing off or on; None where the program
has no such counters. Should move tpot_p50_ms."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "chunk_live_blocks_total", "chunk_table_slots_total")
