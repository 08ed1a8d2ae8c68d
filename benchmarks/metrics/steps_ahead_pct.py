"""Layer: serving loop (serving/cluster/core.py EngineCore.step_once). Share of the window's
steps that were launched BEFORE their predecessor was collected (one step in flight: the host's
work of a step runs under the chip's): driver.metrics.counters ``steps_ahead_total`` over
``engine_steps_total``, both as differences over the window, in percent. Near 100 while the loop
has work; a step that follows an idle loop is not ahead. Counted with tracing off or on; None
where the program has no such counter (a loop that waits for every step where it launches it).
Should move tpot_p50_ms."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "steps_ahead_total", "engine_steps_total")
