"""Layer: kernels. serve_chunk_live_block_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.serve_chunk_live_block_pct import read  # noqa: F401
