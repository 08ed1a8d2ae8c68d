"""End to end, serve cells: 90th percentile, over the requests that finished
inside the window, of (t_finish - t_first_token) / (tokens - 1). Finished in
the window, not started in it: an answer of 512 tokens outlasts any drain a
run can afford, and every token counted was served under the window's load."""
from benchmarks.harness import stats


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    xs = [(q["finish"] - q["first"]) / (q["n_out"] - 1) for q in rec["requests"]
          if q["state"] == "finished" and stats.in_window(q["finish"], w0, w1) and q["n_out"] > 1]
    p = stats.percentile(xs, 90.0)
    return None if p is None else p * 1e3
