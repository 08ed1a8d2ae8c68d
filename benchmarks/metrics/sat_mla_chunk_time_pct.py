"""Layer: kernels (ops/attention/latent_pallas.py), a serving cell of a latent-attention model
whose steps carry prompt chunks for most of their time. Source: device trace. Share of device 0's
busy time under the absorbed CHUNK kernel's own name (``pallas_call(name=...)``: every latent
attention of every step that carries a chunk row is one call: the pool below the chunk, then the
chunk's own vectors, causal), read from the operations the trace lists. None where the name is
not among them: a program without the kernel (the parent), a traced sub-window with no chunk
step, or a kernel too small to be listed. Beside ``sat_mla_decode_time_pct`` it says which of the
two attention kernels a step's time is in. Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

MLA_CHUNK = "dstpu_mla_chunk"


def read(rec):
    return named_share_pct(rec, MLA_CHUNK)
