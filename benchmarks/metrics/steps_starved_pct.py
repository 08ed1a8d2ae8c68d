"""Layer: serving loop (serving/cluster/core.py EngineCore.step_once; counted in
v2/engine_v2.py ``_launch``). Share of the window's steps that were enqueued AFTER the step in
flight had already finished (``jax.Array.is_ready()`` of its output, asked without waiting, just
before the jitted call): the chip ran dry in front of such a step, so the host was the pace for
it. driver.metrics.counters ``steps_starved_total`` over ``engine_steps_total``, as differences
over the window, in percent. steps_ahead_pct says a step was LAUNCHED before its predecessor was
collected; this says whether the launch came in time. A launch with nothing in flight (after an
idle loop) is not starved. It is also the share of steps whose predecessor's end the host saw
late, so for which step_clock_error_ms can leak time from one step into the next. Counted with
tracing off or on; None where the program has no such counter. Should move tpot_p50_ms."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct


def read(rec):
    return window_ratio_pct(rec, "steps_starved_total", "engine_steps_total")
