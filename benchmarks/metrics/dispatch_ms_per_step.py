"""Layer: serving programs (v2/engine_v2.py). Mean length of the SpanTracer span
``engine.dispatch`` (host staging and the asynchronous launch) over the steps that began inside
the window, on the host's clock. Traced run only. Should move tpot_p50_ms."""
from benchmarks.harness import stats


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    xs = [t1 - t0 for name, t0, t1 in rec.get("spans", ())
          if name == "engine.dispatch" and t1 is not None and stats.in_window(t0, w0, w1)]
    m = stats.mean(xs)
    return None if m is None else m * 1e3
