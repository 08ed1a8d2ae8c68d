"""Layer: serving programs (v2/engine_v2.py). The program's own account of when the chip was
busy against the profiler's, in the traced sub-window (the window's last ``trace.window_s``
seconds, as the roofline readers find it): the absolute difference between the seconds under the
ring spans ``step.decode`` + ``step.chunk``, each clipped to the sub-window, and the trace's
``busy_s`` (the union of the device's operations), over the spans counted, in ms a step. The
error bar of decode_step_ms, chunk_step_ms and chunk_step_time_pct, not a target: those time a
step on the host's clock from one wait's return to the next, and this says how far that is from
the device's clock. A step's span holds the gaps between its operations (the trace's idle share
of a closed loop), the time from its enqueue to its first operation where it followed an idle
chip, and, where the host reached a wait after the step had ended, what the step before it lost.
Traced run only; None without a trace or the spans. Should move tpot_p50_ms."""
from benchmarks.metrics.decode_step_ms import step_spans


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    hi = rec["t_window1"]
    lo = hi - tr["window_s"]
    clipped = [min(t1, hi) - max(t0, lo) for t0, t1 in step_spans(rec)
               if min(t1, hi) > max(t0, lo)]
    if not clipped:
        return None
    return 1e3 * abs(sum(clipped) - tr["busy_s"]) / len(clipped)
