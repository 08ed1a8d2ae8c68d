"""Layer: linear attention (ops/linear_attention/gated_delta.py), a serving cell of a model with
Gated DeltaNet layers at saturation. Source: device trace. Share of device 0's busy time under
the one-token state update's own name (``pallas_call(name=...)``; every DeltaNet layer of every
step is a call of the one kernel), read from the operations the trace lists. None where the name
is not among them: a program without the kernel (the parent), or a kernel too small to be
listed. The projections, the conv and the gated norm around the kernel are unnamed fusions and
show only in the remainder; prompt chunks run the chunked rule in plain XLA. Should move
gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

GDN_DECODE = "dstpu_gdn_decode"


def read(rec):
    return named_share_pct(rec, GDN_DECODE)
