"""Layer: serving loop (serving/driver.py _loop). Time the loop slept on its condition with
nothing to step (no request, or stalled on KV blocks): the SpanTracer span ``loop.wait`` summed
over the window, per device program launched in it. The part of the host gap that is the
offered load's and not the program's: an open loop below its knee has some, a saturated loop
none. Host clock, traced run only; None where the program records no such span. Should move
tpot_p50_ms."""
from benchmarks.metrics.host_gap_ms_per_step import spans, sum_ms_per_step


def read(rec):
    if not spans(rec, "engine.launch"):  # no step in the window, or a program without the span
        return None
    return sum_ms_per_step(rec, "loop.wait") or 0.0
