"""Layer: serving loop (v2/scheduler.py). Share of the window's steps that carried a prompt
chunk: driver.metrics.counters ``steps_with_prefill_total`` over ``engine_steps_total``, both as
differences over the window, in percent. A step without one still runs the chunk rows of its
grid (grid_fill_pct). None where the program has no such counter. Should move tpot_p50_ms."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "steps_with_prefill_total", "engine_steps_total")
