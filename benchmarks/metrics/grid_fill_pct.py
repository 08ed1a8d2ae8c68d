"""Layer: serving loop (v2/scheduler.py decides, v2/engine_v2.py pads). Useful slots over
computed slots: driver.metrics.counters ``scheduled_tokens_total`` (decode rows + real prompt
tokens) over ``grid_slots_total`` (the padded grid of every program run: R + Rc x tq slots a
split step, R x steps a fused round), both as differences over the window, in percent. Counted
with tracing off or on; None where the program has no such counters. Should move tpot_p50_ms."""


def window_ratio_pct(rec, top, bottom):
    """100 x the window's difference of one counter over that of another."""
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if top not in c1 or bottom not in c1:
        return None
    den = c1[bottom] - c0.get(bottom, 0)
    return 100.0 * (c1[top] - c0.get(top, 0)) / den if den > 0 else None


def read(rec):
    return window_ratio_pct(rec, "scheduled_tokens_total", "grid_slots_total")
