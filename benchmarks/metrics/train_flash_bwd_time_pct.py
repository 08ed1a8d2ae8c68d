"""Layer: kernels (ops/attention/flash_pallas.py), train cells. Source: device trace. Share of
device 0's busy time in the flash backward kernels (dq and dk/dv, summed), by the kernels' own
names among the operations the trace lists. None where no such name is among them. Should move
train_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct


def read(rec):
    return named_share_pct(rec, "dstpu_flash_bwd")
