"""Layer: serving programs (v2/engine_v2.py). Mean time on the device of a DECODE step (a split
step that carried no prompt chunk; a fused or a verify round counts as one of its own program):
driver.metrics.counters ``decode_step_seconds_total`` over ``decode_steps_timed_total``, both as
differences over the window, in ms. The engine times a step where it collects it
(``InferenceEngineV2._collect``): from the later of the step before it turning ready and its own
enqueue to the return of the wait on its own outputs, on the host's clock, tracing off or on.
What the paged decode kernel, the experts' and the weights' bytes set; a change to the chunk
steps alone must leave it still. None where the program has no such counters. Should move
tpot_p50_ms.

Also the arithmetic the step-kind readers share (every file here is a metric of the index, so a
helper lives in a reader)."""
from benchmarks.metrics.grid_fill_pct import window_ratio_pct

STEP_SPANS = ("step.decode", "step.chunk")


def window_mean_ms(rec, seconds, steps):
    """The window's difference of a seconds counter over that of the count of the steps it
    holds, in ms; None without either, or in a window with no such step."""
    pct = window_ratio_pct(rec, seconds, steps)
    return None if pct is None else pct * 1e3 / 100.0


def step_spans(rec):
    """``(t0, t1)`` of every closed ``step.decode`` / ``step.chunk`` ring span: a step's time on
    the device as the program saw it (``EngineCore._collect_flight``)."""
    return [(t0, t1) for name, t0, t1 in rec.get("spans", ())
            if name in STEP_SPANS and t1 is not None]


def read(rec):
    return window_mean_ms(rec, "decode_step_seconds_total", "decode_steps_timed_total")
