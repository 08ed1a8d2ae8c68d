"""Layer: serving loop (serving/driver.py _loop). What the loop does under its lock between two
steps: the SpanTracer spans ``loop.admit`` (expiry and admission, before and after a step) and
``loop.bookkeeping`` (the gauges and mirrored counters refreshed after every step), summed over
the window, per device program launched in it. Host clock, traced run only; None where the
program records no such spans. Should move tpot_p50_ms."""
from benchmarks.metrics.host_gap_ms_per_step import sum_ms_per_step


def read(rec):
    return sum_ms_per_step(rec, "loop.admit", "loop.bookkeeping")
