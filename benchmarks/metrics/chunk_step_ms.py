"""Layer: serving programs (v2/engine_v2.py). Mean time on the device of a CHUNK step (a split
step that carried a prompt chunk, beside whatever decode rows it had): driver.metrics.counters
``chunk_step_seconds_total`` over ``chunk_steps_timed_total``, both as differences over the
window, in ms; timed as decode_step_ms says. What the chunk grid, the chunk attention and, on a
model with DeltaNet layers, the chunked delta rule set; a change to the decode steps alone must
leave it still. None where the program has no such counters. Should move ttft_p90_ms (a prompt
is its chunk steps)."""
from benchmarks.metrics.decode_step_ms import window_mean_ms


def read(rec):
    return window_mean_ms(rec, "chunk_step_seconds_total", "chunk_steps_timed_total")
