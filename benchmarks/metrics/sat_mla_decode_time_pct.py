"""Layer: kernels (ops/attention/latent_pallas.py), a serving cell of a latent-attention model at
saturation. Source: device trace. Share of device 0's busy time under the absorbed decode
kernel's own name (``pallas_call(name=...)``; every layer of every step is one call), read from
the operations the trace lists. None where the name is not among them: a program without the
kernel (the parent), or a kernel too small to be listed. The projections around it (the two
query projections, ``W_UK`` on the query and ``W_UV`` behind the output) are unnamed fusions and
show only in the remainder; prompt chunks attend under ``dstpu_mla_chunk``. Should move
gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

MLA_DECODE = "dstpu_mla_decode"


def read(rec):
    return named_share_pct(rec, MLA_DECODE)
