"""Layer: serving programs (v2/engine_v2.py). device_wait_ms_per_step in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.device_wait_ms_per_step import read  # noqa: F401
