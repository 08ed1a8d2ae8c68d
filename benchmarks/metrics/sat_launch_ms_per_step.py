"""Layer: serving programs (v2/engine_v2.py). launch_ms_per_step in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.launch_ms_per_step import read  # noqa: F401
