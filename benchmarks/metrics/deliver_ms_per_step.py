"""Layer: serving loop (v2/engine_v2.py step_tokens, serving/cluster/core.py). From the step's
result on the device to its tokens with their clients: mean ``engine.materialize`` (result arrays
to the host, one int a row) plus mean ``step.deliver`` (per token: stream put, stop check,
scheduler feedback; then the capped sequences), over the steps that began inside the window.
SpanTracer ring spans, host clock, traced run only; None where the program records neither.
Should move tpot_p50_ms."""
from benchmarks.metrics.host_gap_ms_per_step import mean_ms


def read(rec):
    parts = [mean_ms(rec, "engine.materialize"), mean_ms(rec, "step.deliver")]
    return None if all(p is None for p in parts) else sum(p or 0.0 for p in parts)
