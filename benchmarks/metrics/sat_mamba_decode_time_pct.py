"""Layer: state-space layers (ops/state_space/mamba.py), a serving cell of a model with Mamba
layers at saturation. Source: device trace. Share of device 0's busy time under the one-token
state update's own name (``pallas_call(name=...)``; every Mamba layer of every step is a call
of the one kernel over the step's decode rows), read from the operations the trace lists. None
where the name is not among them: a program without the kernel (the parent), or a kernel too
small to be listed. Should move gen_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct

MAMBA_DECODE = "dstpu_mamba_decode"


def read(rec):
    return named_share_pct(rec, MAMBA_DECODE)
