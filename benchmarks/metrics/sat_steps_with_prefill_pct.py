"""Layer: serving loop. steps_with_prefill_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.steps_with_prefill_pct import read  # noqa: F401
