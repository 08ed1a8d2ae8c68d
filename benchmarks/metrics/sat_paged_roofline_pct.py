"""Layer: kernels. serve_paged_roofline_pct in a cell at saturation, where
throughput is judged (PERF.md section 2). Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_roofline_pct import read  # noqa: F401
