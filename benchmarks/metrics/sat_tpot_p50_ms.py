"""Layer: serving loop. tpot_p50_ms in a cell at saturation, where throughput is
judged and the time per token follows it (PERF.md section 2). Should move
gen_tok_s."""
from benchmarks.metrics.tpot_p50_ms import read  # noqa: F401
