"""Layer: serving programs. step_clock_error_ms in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.step_clock_error_ms import read  # noqa: F401
