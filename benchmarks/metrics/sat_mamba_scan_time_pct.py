"""Layer: state-space layers (ops/state_space/mamba.py), a serving cell of a model with Mamba
layers at saturation. Source: device trace. Share of device 0's busy time under the chunked
scan's own name (``pallas_call(name=...)``; every Mamba layer of every step that carries a
prompt chunk is a call of the one kernel), read from the operations the trace lists. None where
the name is not among them: a program without the kernel (the parent), or a traced sub-window
with no chunk step in it. The projections, the conv, the three small norms and the softplus
around the kernel are unnamed fusions and matrix products and show only in the remainder.
Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

MAMBA_SCAN = "dstpu_mamba_scan"


def read(rec):
    return named_share_pct(rec, MAMBA_SCAN)
