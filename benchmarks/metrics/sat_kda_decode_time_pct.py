"""Layer: linear attention (ops/linear_attention/kda.py), a serving cell of a model with Kimi
Delta Attention layers at saturation. Source: device trace. Share of device 0's busy time under
the one-token state update's own name, ``dstpu_kda_decode`` (the body of ``dstpu_gdn_decode``
with a decay a key channel; every KDA layer of every step is a call of the one kernel), read
from the operations the trace lists. None where the name is not among them: a program without
the kernel (the parent), or a kernel too small to be listed. The projections, the conv and the
gated norm around the kernel are unnamed fusions and show only in the remainder; prompt chunks
run the chunked rule (``kda_chunked``) in plain XLA. Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

KDA_DECODE = "dstpu_kda_decode"


def read(rec):
    return named_share_pct(rec, KDA_DECODE)
