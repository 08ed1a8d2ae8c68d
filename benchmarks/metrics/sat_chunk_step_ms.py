"""Layer: serving programs. chunk_step_ms in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.chunk_step_ms import read  # noqa: F401
