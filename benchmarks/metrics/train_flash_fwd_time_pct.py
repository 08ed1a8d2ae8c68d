"""Layer: kernels (ops/attention/flash_pallas.py), train cells. Source: device trace. Share of
device 0's busy time in the flash forward kernel (the forward pass and its recomputation in the
backward pass), by the kernel's own name among the operations the trace lists. None where the
name is not among them. Should move train_tok_s."""
from benchmarks.metrics.serve_paged_kernel_time_pct import named_share_pct


def read(rec):
    return named_share_pct(rec, "dstpu_flash_fwd")
