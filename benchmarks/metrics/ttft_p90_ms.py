"""End to end, open-loop serve cells: 90th percentile of the time to the first
token, from when the request was due, over the requests due inside the window
(in a closed loop with more clients than rows it would be queue time). A request that failed misses the percentile
and counts in ``failed``."""
from benchmarks.harness import stats


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    xs = [q["first"] - q["due"] for q in rec["requests"]
          if stats.in_window(q["due"], w0, w1) and q["first"] is not None]
    p = stats.percentile(xs, 90.0)
    return None if p is None else p * 1e3
