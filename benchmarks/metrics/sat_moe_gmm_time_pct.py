"""Layer: expert layer (parallel/moe/grouped.py), a serving cell of an expert model at
saturation. Source: device trace. Share of device 0's busy time under the grouped expert
matmul's own name (``pallas_call(name=...)``; gate, up and down projection of every layer are
calls of the one kernel), read from the operations the trace lists. None where the name is not
among them. The sort, gather and scatter around the kernel are unnamed fusions and show only
in the remainder. Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

MOE_GMM = "dstpu_moe_gmm"


def read(rec):
    return named_share_pct(rec, MOE_GMM)
