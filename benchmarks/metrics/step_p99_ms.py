"""Layer: serving programs (v2/engine_v2.py). 99th percentile of the length of the ring spans
``step.decode`` / ``step.chunk`` that ended in the window, in ms: the longest time on the device
of one step, which is the longest gap a decoding row sat through between two of its tokens (a
chunk step of a long prompt). The per-layer stand-in for an inter-token-latency tail until
``Request`` carries per-token stamps. Traced run only; None where the program records no such
spans. Should move tpot_p90_ms."""
from benchmarks.harness import stats
from benchmarks.metrics.decode_step_ms import step_spans


def read(rec):
    w0, w1 = rec["t_window0"], rec["t_window1"]
    p99 = stats.percentile((t1 - t0 for t0, t1 in step_spans(rec) if stats.in_window(t1, w0, w1)),
                           99.0)
    return None if p99 is None else p99 * 1e3
