"""Layer: serving loop. grid_fill_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.grid_fill_pct import read  # noqa: F401
