"""Layer: serving loop (v2/scheduler.py, called from v2/engine_v2.py _step_device). Mean length of the SpanTracer span ``engine.schedule``
(``scheduler.next_batch()`` and ``drain_capped()``) over the steps that began inside the window, on the host's clock. Traced run only;
None where the program records no such span. Should move tpot_p50_ms."""
from benchmarks.metrics.host_gap_ms_per_step import mean_ms


def read(rec):
    return mean_ms(rec, "engine.schedule")
