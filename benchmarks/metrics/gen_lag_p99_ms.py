"""Layer: load generator (the benchmark's own). 99th percentile of actual submit
time less due time, over the requests due in the window. A late generator
makes ttft_p90_ms read too badly in an open loop (it counts from the due time)."""
from benchmarks.harness import stats


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    xs = [q["submit"] - q["due"] for q in rec["requests"] if stats.in_window(q["due"], w0, w1)]
    p = stats.percentile(xs, 99.0)
    return None if p is None else p * 1e3
