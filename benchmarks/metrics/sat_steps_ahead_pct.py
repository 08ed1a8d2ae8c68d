"""Layer: serving loop. steps_ahead_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.steps_ahead_pct import read  # noqa: F401
