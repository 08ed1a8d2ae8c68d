"""Layer: serving loop (serving/driver.py, v2/scheduler.py). Median of t_admitted
less due time over the requests due in the window. Should move ttft_p90_ms."""
from benchmarks.harness import stats


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    xs = [q["admitted"] - q["due"] for q in rec["requests"]
          if stats.in_window(q["due"], w0, w1) and q["admitted"] is not None]
    p = stats.median(xs)
    return None if p is None else p * 1e3
