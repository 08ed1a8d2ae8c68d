"""Layer: serving loop. host_gap_ms_per_step (an upper bound on the idle the host causes, not
the device's idle time: see that reader) in a cell at saturation, where throughput is judged
(PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.host_gap_ms_per_step import read  # noqa: F401
