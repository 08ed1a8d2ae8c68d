"""Layer: serving loop. steps_starved_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.steps_starved_pct import read  # noqa: F401
