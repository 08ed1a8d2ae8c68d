"""Layer: train step. Tokens per second (the median block's, as train_tok_s)
times the benchmark's own operations per token (forward and backward, causal
attention counted with heads x head size, nothing recomputed) over chips x the
published bf16 peak. An end-to-end utilization, not a kernel's roofline share."""
from benchmarks.harness import flops, peaks, stats


def read(rec):
    tok_s = stats.median_rate(rec["marks"])
    need = flops.train_flops_per_token(rec["hf"], rec["seq_len"])
    peak = peaks.device_peaks(rec["device_kind"]).bf16_flops * rec["chips"]
    return None if tok_s is None else 100.0 * tok_s * need / peak
